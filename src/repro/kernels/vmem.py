"""The scoped-VMEM cap shared by the kernels that hold whole blocks."""

#: Scoped-VMEM cap for the kernels that hold whole (n, n) blocks. The
#: compiler's default cap on v5e is 16 MiB, and a 1024-wide block
#: inverse needs about 37 MiB of temporaries besides its double-buffered
#: operands; a v5e core has 128 MiB of VMEM.
VMEM_LIMIT_BYTES = 100 * 2 ** 20
