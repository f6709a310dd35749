"""Bytes the traced decode steps must move over the decode module's
device time x the HBM peak, in % (``bench/model_cost.py``)."""

from bench.live_spans import decode_roofline as read  # noqa: F401
