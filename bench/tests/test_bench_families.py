"""The family seam: a configuration's ``model_type`` selects the module
that builds, checks and counts its model.  The dense family gives the
numbers and weights the harness gave before it had families, an unknown
family fails before any weight is made, and a family that exists only as
a new file is served end to end."""

import hashlib
import json
import re
import time
from pathlib import Path

import jax
import numpy as np
import pytest

from bench import harness, modeldef
from bench.tests.tiny import CHAT, CONTIGUOUS, config

CONFIGS = Path(__file__).resolve().parents[1] / "configs"
E2E = [{"name": "tokens_per_s", "unit": "tokens/s"}]

# sha256 of every leaf of the tiny configuration's weights (path, dtype,
# shape and bytes, in tree order), recorded from the dense harness as it
# was before the seam
DENSE_DIGESTS = {
    5: "6ca7f948a56557fae198e44bf29000b3d9b8ca9dc1a8f1734ab3f57d4869e85c",
    2**31 + 5:
        "4cff86d5e6d58b7c96a8ab717c193d0446efdba25548a9d079b7198970bb4892",
}

# a family that only this test writes: the dense code under another
# model_type, noting in the configuration each piece the harness asks for
NEW_FAMILY = '''
from bench.families import qwen2 as dense


def model_config(cfg):
    cfg["used"].append("model_config")
    return dense.model_config(cfg)


def init_fn(cfg):
    cfg["used"].append("init_fn")
    return dense.init_fn(cfg)


class Reference(dense.Reference):
    def __init__(self, cfg):
        cfg["used"].append("Reference")
        super().__init__(cfg)


class Shapes:
    @staticmethod
    def of(cfg):
        cfg["used"].append("Shapes")
        return dense.Shapes.of(cfg)
'''


def digest(tree) -> str:
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        a = np.asarray(leaf)
        for part in (jax.tree_util.keystr(path), str(a.dtype), str(a.shape)):
            h.update(part.encode())
        h.update(a.tobytes())
    return h.hexdigest()


def run(cfg, **kw):
    return harness.run_cell(
        cfg=cfg, mix=CHAT, cell=CONTIGUOUS, metrics=E2E, seed=2**31 + 9,
        seconds=0.2, trace=False, t_process=time.monotonic(),
        require_tpu=False, **kw)


def test_qwen_counts_are_the_dense_harness_numbers():
    cfg = json.loads((CONFIGS / "qwen2.5-3b.json").read_text())
    sh = modeldef.family(cfg).Shapes.of(cfg)
    assert sh.params == 3085938688
    assert sh.weight_bytes == 6171877376
    assert sh.kv_bytes_per_token == 36864
    assert sh.decode_flops([300, 700]) == 12637700096
    assert sh.decode_bytes([300, 700]) == 6208815104
    assert sh.prefill_flops(256) == 1430884188160


@pytest.mark.parametrize("seed", sorted(DENSE_DIGESTS))
def test_weights_are_bitwise_the_dense_harness_weights(seed):
    assert digest(modeldef.make_params(config(), seed)) == DENSE_DIGESTS[seed]


@pytest.mark.parametrize("kind,looked_for", [
    ("deepseek_v2", "bench/families/deepseek_v2.py"),
    (None, "bench/families/<model_type>.py")], ids=["unknown", "missing"])
def test_unknown_family_fails_before_weights_are_made(kind, looked_for,
                                                      monkeypatch):
    made = []
    monkeypatch.setattr(modeldef, "make_params",
                        lambda *a: made.append(a))
    cfg = config(model_type=kind)
    with pytest.raises(LookupError, match=re.escape(looked_for)):
        run(cfg)
    assert made == []


def test_family_added_as_a_new_file_is_served_end_to_end(tmp_path,
                                                         monkeypatch):
    # '.' and '-' in model_type become '_' in the file's name
    (tmp_path / "test_dense_v0.py").write_text(NEW_FAMILY)
    monkeypatch.setattr(modeldef, "FAMILIES", tmp_path)
    cfg = config(model_type="test-dense.v0", used=[])
    res = run(cfg)
    assert res["correct"], res["checks"]
    assert res["metrics"]["tokens_per_s"]["value"] > 0
    assert set(cfg["used"]) == {"model_config", "init_fn", "Reference",
                                "Shapes"}
