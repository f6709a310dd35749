"""95th percentile, ms, of a paper model's measured execution on the live
plane (program span ``live.execute``) in cells that report decision_s."""

from bench.live_spans import execute_ms_p95 as read  # noqa: F401
