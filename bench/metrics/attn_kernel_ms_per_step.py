"""Device time of the attention kernel's own operations in the train
program, per step: the Pallas calls (``pallas_call`` in the operation's
name stack) under the ``attn`` scope of ``jit_train_step``, which are the
flash kernel's forward, recomputed forward, dq and dkv calls
(``models/layers.attention``). The rest of ``attn_ms_per_step`` is the
QKV and O projections, rotary and the layout copies around the kernel.

None where the train program runs no such operation, as where attention
takes the XLA path, and where the trace cannot be found or decoded."""

import gzip
import traceback

import scopes
import trace_reduce

PROGRAM = "jit_train_step"
SCOPE = "attn"
#: the name-stack component of an operation a Pallas kernel lowers to
KERNEL = "pallas_call"


def _window(space):
    """The longest ``bench:window`` span on the host, in seconds."""
    window = None
    for plane in space.planes:
        if not plane.name.startswith("/host:"):
            continue
        names = {e.key: e.value.name for e in plane.event_metadata}
        for line in plane.lines:
            for ev in line.events:
                if names.get(ev.metadata_id) == trace_reduce.WINDOW:
                    s = line.timestamp_ns * 1e-9 + ev.offset_ps * 1e-12
                    iv = (s, s + ev.duration_ps * 1e-12)
                    if window is None or iv[1] - iv[0] > \
                            window[1] - window[0]:
                        window = iv
    return window


def _is_kernel(stack: str) -> bool:
    """``stack``: the ``tf_op`` stat, ``<name stack>:<op type>``."""
    stack = stack.split(";")[0]
    return (KERNEL in stack.rsplit(":", 1)[0].split("/")
            and scopes.scope_of(stack) == SCOPE)


def load(path: str):
    """Seconds of kernel operations in the train program within the
    window, averaged over the TPU devices; None for none."""
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as f:
        space = scopes._xspace_class().FromString(f.read())
    window = _window(space)
    total, n_dev = 0.0, 0
    for plane in space.planes:
        if not trace_reduce.DEVICE_PLANE.match(plane.name):
            continue
        n_dev += 1
        stat_names = {e.key: e.value.name for e in plane.stat_metadata}
        meta = {e.key: e.value for e in plane.event_metadata}
        lines = {line.name: line for line in plane.lines}
        programs = {}
        for ev in getattr(lines.get("XLA Modules"), "events", ()):
            m = scopes._MODULE.match(meta[ev.metadata_id].name)
            if m:
                programs[int(m.group(2))] = m.group(1)
        ops = lines.get("XLA Ops")
        mine = {}                       # metadata id -> a kernel op?
        for ev in getattr(ops, "events", ()):
            if ev.metadata_id not in mine:
                md = meta[ev.metadata_id]
                stats = {stat_names.get(s.metadata_id): s
                         for s in md.stats}
                tf_op, pid = stats.get("tf_op"), stats.get("program_id")
                # control flow encloses other operations (scopes.load)
                mine[ev.metadata_id] = (
                    not trace_reduce.CONTAINER.match(md.name)
                    and tf_op is not None and pid is not None
                    and programs.get(pid.uint64_value) == PROGRAM
                    and _is_kernel(tf_op.str_value))
            if not mine[ev.metadata_id]:
                continue
            s = ops.timestamp_ns * 1e-9 + ev.offset_ps * 1e-12
            e = s + ev.duration_ps * 1e-12
            if window is not None:
                s, e = max(s, window[0]), min(e, window[1])
            total += max(e - s, 0.0)
    if not total or not n_dev:
        return None
    return total / n_dev


def read(r):
    if "_attn_kernel_s" not in r.__dict__:
        try:
            path = scopes.trace_path(r)
            r._attn_kernel_s = load(path) if path else None
        except Exception:
            # a reader returns None and never fails the run: say why
            traceback.print_exc()
            r._attn_kernel_s = None
    s = r._attn_kernel_s
    if not s or not r.window.steps:
        return None
    return 1e3 * s / r.window.steps
