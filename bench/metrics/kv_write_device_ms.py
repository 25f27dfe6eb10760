"""Device time of the whole cache writes of admission in the traced span
(the contiguous row splice ``jit_splice_cache``; the paged
``jit_write_page`` and ``jit_admit_paged_slot``), per whole prefill run
there (``jit_prefill_padded``, ``jit_prefill_continue``).  None where
either kind of program is missing."""

WRITES = ("jit_splice_cache", "jit_write_page", "jit_admit_paged_slot")
PREFILLS = ("jit_prefill_padded", "jit_prefill_continue")


def read(ctx):
    if ctx.trace is None:
        return None
    prog_s, runs = ctx.trace["program_s"], ctx.trace["program_runs"]
    prefills = sum(runs.get(p, 0) for p in PREFILLS)
    if not prefills or not any(p in runs for p in WRITES):
        return None
    return 1e3 * sum(prog_s.get(p, 0.0) for p in WRITES) / prefills
