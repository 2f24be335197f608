"""The vocab head's projection fused with its cross-entropy.

Training's per-token NLL over a large vocabulary without an fp32
(tokens x vocab) tensor in HBM, forward or backward.
"""
from repro.kernels.fused_ce.bwd import fused_ce_bwd_call
from repro.kernels.fused_ce.fused_ce import fused_ce_fwd_call
from repro.kernels.fused_ce.ops import token_nll
from repro.kernels.fused_ce.ref import token_nll_ref

__all__ = ["fused_ce_fwd_call", "fused_ce_bwd_call", "token_nll",
           "token_nll_ref"]
