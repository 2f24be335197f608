"""CPU tests of the benchmark's own arithmetic and of its checks, at tiny
sizes.  Run with ``JAX_PLATFORMS=cpu python3 -m pytest bench/tests``."""
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY = {"name": "tiny", "family": "lm", "n_layers": 2, "d_model": 128,
        "n_heads": 4, "n_kv_heads": 2, "head_dim": 0, "d_ff": 256,
        "vocab_size": 512, "max_seq_len": 512, "act": "swiglu",
        "norm": "rmsnorm", "rope": "rope", "rope_theta": 10000.0,
        "tie_embeddings": False, "remat": True, "scan_layers": True,
        "attention": {"kind": "flow", "chunk_size": 32}}
