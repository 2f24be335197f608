"""Names of the train step's layers, as the compiled program carries them.

Each name is opened once, as a ``jax.named_scope``, where its layer is
entered.  The scope reaches the ``op_name`` metadata of every HLO
instruction the layer lowers to, through ``jit``, ``value_and_grad``,
``lax.scan`` and ``jax.checkpoint``; it changes no computation.  The
benchmark (``bench/scopes.py``) joins a device trace with the compiled
step's metadata by these names to split the step's device time by layer,
so a name changes only together with it.

The ``step.`` prefix keeps every name apart from the path components JAX
generates itself (``jit(norm)`` from ``global_norm``, ``while``, ``body``,
primitive names).
"""
EMBED = "step.embed"
NORM = "step.norm"
MIXER = "step.mixer"
FFN = "step.ffn"
HEAD = "step.head"
OPTIMIZER = "step.optimizer"

ALL = (EMBED, NORM, MIXER, FFN, HEAD, OPTIMIZER)
