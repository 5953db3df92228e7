"""host_gap_ms (ms/solve, device trace), and host_gap_ms.short, its split that moves solve_s.short. Layer: solver host path."""

from benchmark.host_path import host_gap_ms as read  # noqa: F401
