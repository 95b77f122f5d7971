"""Backend compiles that finished during the window's ticks (count; 0 when set-up warmed every shape). Source: the batcher's tick ring."""
from benchmark.tick_readers import compiles_in_window as read  # noqa: F401
