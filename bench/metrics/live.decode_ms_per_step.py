"""ms per decode step of a live LLM query (program span ``live.decode``
over its ``steps``)."""

from bench.live_spans import decode_ms_per_step as read  # noqa: F401
