"""Mean device time of the whole prefill runs in the traced span: the
engine's pad-masked prefill (``jit_prefill_padded``) and the paged
continuation after a prefix hit (``jit_prefill_continue``).  None where
no program of those names ran."""

PROGRAMS = ("jit_prefill_padded", "jit_prefill_continue")


def read(ctx):
    if ctx.trace is None:
        return None
    secs = sum(ctx.trace["program_s"].get(p, 0.0) for p in PROGRAMS)
    runs = sum(ctx.trace["program_runs"].get(p, 0) for p in PROGRAMS)
    return 1e3 * secs / runs if runs else None
