"""``peak_bytes_in_use`` of the fullest device after the window, GiB."""


def read(r):
    return r.peak_bytes / 2 ** 30
