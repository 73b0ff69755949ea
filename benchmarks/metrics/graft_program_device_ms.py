"""Read by the program-time reader beside this file: its `module` names the
admission graft program where that metric's names the paged decode program."""

from metrics.decode_program_device_ms import read  # noqa: F401
