"""A bind's weights from the seed: the image's staged init against the
eager ``bundle.init``, what a bind compiles after the image's ``warm()``,
and the staged program's temp memory against depth."""

from __future__ import annotations

import dataclasses

import jax
import numpy as np
import pytest

from repro.configs.base import get_config, get_smoke_config
from repro.core.images import ExecutableRegistry, PayloadImage
from repro.models.api import build_model

ARCHS = ["smollm-360m", "granite-moe-3b-a800m"]
KINDS = ["serve", "prefill", "decode"]


def _params(kind, inputs):
    """The params in what an image kind's ``make_inputs`` returns."""
    return inputs if kind == "serve" else inputs[0]


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("arch", ARCHS)
def test_staged_init_matches_eager_init(arch, kind):
    """Every layer leaf bitwise; the rest (embedding, final norm, an
    untied head) within one float32 ulp, where XLA folds the scale."""
    exe = ExecutableRegistry().pull(PayloadImage(arch, "smoke", kind))
    key = jax.random.key(2 ** 31 + 3)
    tel = {}
    got = _params(kind, exe.make_inputs(key, tel))
    assert tel == {"weights_compiles": 1}       # the image was not warmed
    want = build_model(get_smoke_config(arch)).init(key)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for g, w in zip(jax.tree.leaves(got["layers"]),
                    jax.tree.leaves(want["layers"])):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    for name in set(want) - {"layers"}:
        for g, w in zip(jax.tree.leaves(got[name]),
                        jax.tree.leaves(want[name])):
            g, w = np.asarray(g), np.asarray(w)
            assert g.dtype == w.dtype == np.float32, name
            assert (np.abs(g - w) <= np.spacing(np.abs(w))).all(), name


@pytest.mark.parametrize("kind", KINDS)
def test_bind_after_warm_compiles_no_weights(kind):
    """After ``warm()`` a bind dispatches the compiled init, and each bind
    gets fresh buffers from its own key."""
    exe = ExecutableRegistry().pull(PayloadImage("smollm-360m", "smoke",
                                                 kind))
    exe.warm()
    binds = []
    for seed in (7, 7, 8):
        tel = {}
        binds.append(_params(kind, exe.make_inputs(jax.random.key(seed),
                                                   tel)))
        assert tel == {"weights_compiles": 0}
    a, again, other = (np.asarray(p["embed"]) for p in binds)
    np.testing.assert_array_equal(a, again)
    assert not np.array_equal(a, other)
    binds[0]["embed"].delete()                  # no buffer is shared
    np.testing.assert_array_equal(np.asarray(binds[1]["embed"]), again)


def _init_temp_bytes(num_layers: int) -> int:
    cfg = dataclasses.replace(get_config("granite-moe-3b-a800m"),
                              num_layers=num_layers)
    init = jax.jit(build_model(cfg).init)
    compiled = init.lower(jax.random.key(0)).compile()
    return compiled.memory_analysis().temp_size_in_bytes


def test_staged_init_temp_does_not_grow_with_depth():
    """Compiled for the CPU at granite-moe-3b-a800m's published widths:
    the init makes one layer group at a time, so its temp holds one
    group's random bits whatever the depth (a ``vmap`` over the groups
    doubles it from 2 to 4 layers, and needs 10.7 GB of temp at 16)."""
    two, four = _init_temp_bytes(2), _init_temp_bytes(4)
    assert 0 < four <= 1.2 * two, (two, four)
