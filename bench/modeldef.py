"""A configuration file (``bench/configs/<name>.json``, Hugging Face key
names), the family that serves it, and weights made by the benchmark
itself from the seed.

The configuration's ``model_type`` selects its family module
(``bench/families/<model_type>.py``, whose contract is in
``bench/families/__init__.py``): the program's ``ModelConfig``, the
weights' layout, the reference and the counts.  What follows is the
same for every family.

The weights are the benchmark's, not the program's: one jitted call of
the family's ``init_fn`` makes every leaf on the device in the served
dtype, in the program's parameter layout (checked against
``jax.eval_shape(Model.init)``), so the program and the reference read
the same numbers and neither made them.
"""

from __future__ import annotations

import functools
import importlib.util
import json
import re
import sys
from pathlib import Path

import jax

HERE = Path(__file__).resolve().parent
FAMILIES = HERE / "families"


def load_config(name: str) -> dict:
    return json.loads((HERE / "configs" / f"{name}.json").read_text())


def family(cfg: dict):
    """The family module that ``cfg["model_type"]`` names, loaded by path.
    Raises ``LookupError`` naming the file looked for where the key or
    the file is missing."""
    kind = cfg.get("model_type")
    name = re.sub(r"[.-]", "_", kind) if kind else "<model_type>"
    path = FAMILIES / f"{name}.py"
    if not kind or not path.is_file():
        shown = path.relative_to(HERE.parent) \
            if path.is_relative_to(HERE.parent) else path
        raise LookupError(f"configuration {cfg.get('name')!r} has model_type "
                          f"{kind!r}: no family module {shown}")
    return _load(path)


@functools.lru_cache(maxsize=None)
def _load(path: Path):
    """A family module, run once per file however often it is asked for."""
    spec = importlib.util.spec_from_file_location(
        f"bench_family_{path.stem}", path)
    mod = importlib.util.module_from_spec(spec)
    # registered first: dataclasses look their module up while it runs
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def seed_key(seed: int):
    """A PRNG key from any non-negative seed, 32 bits or more."""
    key = jax.random.PRNGKey(seed % (1 << 31))
    return jax.random.fold_in(key, seed >> 31)


def check_layout(init_fn, model) -> None:
    """The tree a family's ``init_fn(cfg)`` makes must be the program's
    parameter layout."""
    want = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    got = jax.eval_shape(init_fn, jax.random.PRNGKey(0))
    sig = lambda t: jax.tree.map(lambda a: (a.shape, str(a.dtype)), t)
    if jax.tree.structure(want) != jax.tree.structure(got) or \
            sig(want) != sig(got):
        raise ValueError(f"parameter layout differs from the program's:\n"
                         f"program {sig(want)}\nbenchmark {sig(got)}")


def make_params(cfg: dict, seed: int):
    params = jax.jit(family(cfg).init_fn(cfg))(seed_key(seed))
    return jax.block_until_ready(params)
