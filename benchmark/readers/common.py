"""Readers every cell has. A reader takes the run's record and returns a
number, or None where it finds nothing to read."""


def setup_s(record):
    """Process start to the start of the measured window: launch, chip
    open, weights, compilation or cache loads, warm-up."""
    return record["worker"]["window_start"] - record["process_start"]


def programs_loaded_s(record):
    """Seconds the worker spent compiling programs or loading them from
    the persistent cache (jax's monitoring events)."""
    return record["worker"]["programs_loaded_s"]
