"""A whole run on the CPU at a tiny size, past the look for a chip: the
window compiles nothing, the check passes on the program, and it fails
on the control and on each fault planted in the timed path."""

import re
import time

import jax.numpy as jnp
import pytest

from bench import harness
from bench.tests.tiny import CHAT, CONTIGUOUS, DOCS, PAGED, config

E2E = [{"name": n, "unit": u} for n, u in [
    ("tokens_per_s", "tokens/s"), ("ttft_p90_s", "s"), ("tpot_p90_ms", "ms"),
    ("setup_s", "s"), ("window_compiles", "count")]]


def run(cell=CONTIGUOUS, mix=CHAT, **kw):
    kw = {"metrics": E2E, "seed": 2**31 + 3, "trace": False, **kw}
    return harness.run_cell(
        cfg=config(), mix=mix, cell=cell, seconds=0.2,
        t_process=time.monotonic(), require_tpu=False, **kw)


CELLS = pytest.mark.parametrize("cell,mix", [(CONTIGUOUS, CHAT),
                                             (PAGED, DOCS)],
                                ids=["contiguous", "paged"])


@CELLS
def test_sound_run_is_correct_and_compiles_nothing_in_the_window(cell, mix):
    res = run(cell, mix)
    assert res["correct"], res["checks"]
    assert res["metrics"]["window_compiles"]["value"] == 0
    assert res["failed"] == 0 and res["attempted"] >= cell["requests_per_call"]
    assert list(res)[-1] == "checks"
    assert res["device"]["platform"] == "cpu"


@CELLS
@pytest.mark.parametrize("control", ["int8", "fp8"])
def test_control_in_lower_precision_is_not_correct(control, cell, mix):
    res = run(cell, mix, controls=(control,))
    assert not res["correct"], res["checks"]
    for stat in ("max", "mean"):
        assert res["checks"][f"program_{stat}_logit_gap"]["value"] <= \
            cell["limits"][f"{stat}_logit_gap"]


def _faults():
    from repro.models import Model

    class StateUnchanged(Model):
        """A decode step that returns the cache it was given."""

        def decode_step(self, params, tokens, cache):
            logits, _ = super().decode_step(params, tokens, cache)
            return logits, cache

    class HalfBatch(Model):
        """The second half of the rows left out: they get the first
        half's logits."""

        def decode_step(self, params, tokens, cache):
            logits, cache = super().decode_step(params, tokens, cache)
            h = logits.shape[0] // 2
            return logits.at[h:2 * h].set(logits[:h]), cache

    class TokenAltered(Model):
        """Row 0's next token moved by one where it is produced."""

        def decode_step(self, params, tokens, cache):
            logits, cache = super().decode_step(params, tokens, cache)
            top = jnp.argmax(logits[0])
            nxt = (top + 1) % logits.shape[1]
            return logits.at[0, nxt].set(logits[0, top] + 1.0), cache

    return {"state_unchanged": StateUnchanged, "half_batch": HalfBatch,
            "token_altered": TokenAltered}


@CELLS
@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "token_altered"])
def test_fault_in_the_timed_path_is_not_correct(fault, cell, mix):
    res = run(cell, mix, model_cls=_faults()[fault])
    assert not res["correct"], res["checks"]


def test_traced_run_splits_idle_by_span_and_labels_its_gaps():
    per_layer = [{"name": n, "unit": "%"} for n in (
        "device_idle_share", "idle_admit_share", "idle_tick_share")]
    res = run(metrics=per_layer, trace=True)
    assert res["correct"], res["checks"]
    got = {k: v["value"] for k, v in res["metrics"].items()}
    assert set(got) == {m["name"] for m in per_layer}
    assert 0 < got["idle_admit_share"] + got["idle_tick_share"] <= \
        got["device_idle_share"] + 1e-9
    gaps = res["breakdown"]["idle_gaps"]
    assert gaps and all(re.match(r"(serve\.\w+|serve call): ", n)
                        for n, _ in gaps)
