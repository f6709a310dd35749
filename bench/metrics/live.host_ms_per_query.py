"""ms of host work per live LLM query (program span ``live.serve`` outside
``live.prefill`` and ``live.decode``) in cells that report
scored_queries_per_s."""

from bench.live_spans import host_ms_per_query as read  # noqa: F401
