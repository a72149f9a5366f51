"""TPU kernels (Pallas) + XLA reference implementations.

The hot ops of the ML stack: blockwise (flash) attention, ring attention for
sequence parallelism (absent from the reference — SURVEY §5.7 greenfield), GAE
scans for RL; and a Mamba-2 mixer's three kernel pairs (``models/mamba.py``):
the chunked scan (``ssd.ssd_scan``), the short causal convolution with its
silu (``conv.conv_silu``) and the gated norm a group
(``gated_norm.gated_rms_norm``), and the manifold-constrained
hyper-connection around a branch of a block whose residual path is several
streams (``hyper_connection.hyper_connection``: ``models/llama.py``'s
``HyperConnection``), and the rotary embedding with a per-head norm before
it over a layer's q and k together as their projections wrote them
(``rope.rope_qk``: ``models/llama.py``'s ``LlamaAttention``, whose
``apply_rope`` is its ``jax.numpy`` form), and the two gated delta rules'
chunked scans — Kimi Delta Attention's, a decay a channel (``kda.kda_scan``:
``models/kda.py``), and Gated DeltaNet's, one decay a head and grouped key
heads (``gdn.gdn_scan``: ``models/gdn.py``) —, each beside its ``jax.numpy``
form, which runs where
the op's docstring says the kernels do not apply.  Every op has an
XLA fallback used automatically off-TPU and for verification.
"""

from ray_tpu.ops.attention import flash_attention, mha_reference, ring_attention
from ray_tpu.ops.gae import discounted_returns, gae_advantages
from ray_tpu.ops.gdn import gdn_scan, gdn_scan_xla
from ray_tpu.ops.kda import kda_scan, kda_scan_xla

__all__ = [
    "flash_attention", "mha_reference", "ring_attention",
    "gae_advantages", "discounted_returns",
    "kda_scan", "kda_scan_xla", "gdn_scan", "gdn_scan_xla",
]
