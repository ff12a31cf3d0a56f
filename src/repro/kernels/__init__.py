"""Pallas TPU kernels for the paper's compute hot-spots.

RePAST's hardware contributions are (i) the bit-sliced VMM datapath,
(ii) the O(1) in-array matrix inversion, (iii) the fused MM+INV circuit.
Their TPU-native counterparts (see each module's docstring for the
mapping argument):

  bitslice_mm       hi/lo bf16 sliced matmul, fp32 S+A in VMEM
  neumann_inv       VMEM-resident composed-precision block inverse
  fused_gram_solve  fused Gram-accumulate + inverse (never HBM the Gram)
  fused_precond     pooled two-sided WU VMM (Eqn. 3) with the
                    trust-region dot accumulated in the same pass —
                    the fused VMM⊕INV crossbar-group image (Sec. V)

Beside them, ``flash_attention`` runs the model's causal self-attention
by blocked online softmax on a TPU (forward, dq and dkv kernels), and
``grouped_matmul`` the dropless expert layer's grouped matmuls and
per-expert Grams (megablox on a TPU).

Validated in interpret mode on CPU against ``ref.py`` oracles
(tests/test_kernels.py sweeps shapes/dtypes).
"""

from repro.kernels.ops import (  # noqa: F401
    bitslice_mm,
    fused_gram_inv,
    fused_precond,
    neumann_inv,
    on_tpu,
)
