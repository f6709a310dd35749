"""ms of a live LLM query's prefill (program span ``live.prefill``)."""

from bench.live_spans import prefill_ms_per_query as read  # noqa: F401
