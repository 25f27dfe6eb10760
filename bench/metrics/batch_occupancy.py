"""Share of decode slot-ticks that held a live request over the window:
``decode_slot_ticks / (total_ticks * slots)``."""


def read(ctx):
    live = sum(c.report.decode_slot_ticks for c in ctx.calls)
    held = sum(c.report.total_ticks * c.report.slots for c in ctx.calls)
    return 100.0 * live / held if held else None
