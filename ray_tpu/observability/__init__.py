"""Observability subsystems: distributed tracing (tracing.py) and
performance introspection — engine phase spans, compile-event tracking,
device-memory accounting, on-demand XProf capture, and the local
context-manager profiling helpers (profiling.py; ray_tpu.util exports
profile_trace / annotate)."""
