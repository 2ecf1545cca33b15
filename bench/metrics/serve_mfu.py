"""serve_mfu: the served model's share of the chip's int8 peak.

The model operations of every token processed in the traced stretch
(``bench/drivers/serve.py``, ``traced_work``: 2 x the layers' matmul
weights for each prompt and output token, attention over each token's
context, the head for each token that yields logits), over the traced
window, the chips and the int8 peak.
"""
from bench.trace import share


def read(view):
    traced = view.record.get("traced")
    if not traced:
        return None
    rate = traced["model_ops"] / view.trace.window_s()
    return share(rate, view.chips * view.peaks["int8_ops_per_s"])
