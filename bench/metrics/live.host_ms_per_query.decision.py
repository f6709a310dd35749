"""ms of host work per live query (program span ``live.serve`` outside
its execution) in cells that report decision_s."""

from bench.live_spans import host_ms_per_query as read  # noqa: F401
