"""Compiles inside the traced window: the program's ``repro.compile``
spans there (one per executable compiled or loaded from the persistent
cache).  Every shape is warmed before the window, so this reads 0."""
from harness.named import counts_compiles, spans


def read(ctx):
    if not counts_compiles():
        return None
    return len(spans(ctx.trace, "repro.compile"))
