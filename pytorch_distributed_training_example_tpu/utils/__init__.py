"""Config, logging/metrics, telemetry (spans, goodput), and guard-rail utilities."""
