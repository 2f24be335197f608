"""Plain references, one module per architecture, found by name.

A configuration file names its reference under ``"reference"``; the
drivers load ``bench/reference/<reference>.py`` through
``harness.reference(config)``, so a new architecture adds its reference as
one new file here and changes no driver.  A reference imports nothing of
the program and takes nothing the program made except the weights tree
(``bench/weights.py``, in the layout the program's ``init`` gives).  It
exports:

``forward(params, tokens, model, quant=None)``
    Logits (B, N, vocab), fp32, of int32 ``tokens`` (B, N); ``model`` is
    the configuration file's ``model`` dict.

``adamw_steps(params, batches, model, opt, quant=None)``
    ``len(batches)`` AdamW steps from ``params`` on batches of
    ``{"inputs", "targets"}`` (B, N) with the traffic file's ``optimizer``
    dict ``opt``; returns (losses, the first step's clipped gradient, the
    parameters after the last step).

``quant`` names the control's lower precision (``"fp8"``); ``None`` is the
reference itself.
"""
