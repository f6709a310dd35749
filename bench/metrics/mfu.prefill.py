"""Model FLOPs of the traced prefills over the prefill module's device
time x the bf16 peak, in % (``bench/model_cost.py``)."""

from bench.live_spans import mfu_prefill as read  # noqa: F401
