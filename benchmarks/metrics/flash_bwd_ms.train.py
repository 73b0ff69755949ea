"""Read by the shared kernel-time reader beside this file."""

from metrics.kernel_ms import read  # noqa: F401
