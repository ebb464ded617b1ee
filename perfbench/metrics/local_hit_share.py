"""layer: plugin emulation (the plugin-local decision map).  source:
program_counter (the client's own counts).  moves: decision_p50_ms.
Share of the window's pods answered from the local map with no wire call."""


def read(ctx):
    w = ctx.window
    return 100.0 * w.hits / w.asked if w.asked else None
