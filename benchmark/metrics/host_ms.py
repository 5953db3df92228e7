"""host_ms (ms/solve, device trace), and host_ms.short, its split that moves solve_s.short. Layer: solver host path."""

from benchmark.host_path import host_ms as read  # noqa: F401
