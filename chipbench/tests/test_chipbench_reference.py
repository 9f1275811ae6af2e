"""The plain float32 references against the program's own float32 paths
(``lm.forward``, ``lm.train_loss``) at a small size on the CPU, on
weights from ``chipbench/weights.py``.

Tolerances: both sides compute in float32 with every matrix product at
full precision; they differ only in the order of sums (the reference
scans the SSD recurrence token by token where the program's forward
runs it in chunks, and blocks attention over queries), so logits agree
to a few float32 ulps of their size, ~1e-6 here: 1e-5 leaves room."""


import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import weights
from chipbench.reference import dense_gqa, mamba2
from chipbench.tests import tiny
from repro.models import lm
from repro.models.config import ModelConfig

F32 = dict(torch_dtype="float32")
CASES = [(dense_gqa, dict(tiny.DENSE, sliding_window=8, **F32)),
         (mamba2, dict(tiny.SSM, **F32))]
SEED = 2**33 + 5            # past 32 bits: the key keeps both words


def setup(mod, c):
    cfg = ModelConfig(**mod.program_config(c))
    lay = mod.layout(c)
    weights.check_layout(lay, lm.abstract_params(cfg))
    return cfg, lay, weights.make(lay, SEED)


def tokens(vocab, shape=(2, 24)):
    return jnp.asarray(np.random.default_rng(0).integers(0, vocab, shape),
                       jnp.int32)


@pytest.mark.parametrize("mod,c", CASES, ids=["dense_gqa", "mamba2"])
def test_reference_logits_match_the_program(mod, c):
    cfg, lay, params = setup(mod, c)
    t = tokens(c["vocab_size"])           # 24 tokens: past the window of 8
    prog = lm.forward(cfg, params, {"tokens": t}, remat="none")[0]
    words = jnp.asarray(weights.seed_words(SEED))

    def get(path, layer):
        return weights.layer_leaf(lay, path, words, layer).astype(jnp.float32)

    ref = jax.jit(lambda x: mod.logits(c, get, x))(t)
    scale = float(jnp.abs(ref).max())
    assert scale > 0.1
    assert float(jnp.abs(prog - ref).max()) < 1e-5 * max(1.0, scale)


@pytest.mark.parametrize("mod,c", CASES, ids=["dense_gqa", "mamba2"])
def test_layer_by_layer_weights_equal_the_whole_tree(mod, c):
    _, lay, params = setup(mod, c)
    words = jnp.asarray(weights.seed_words(SEED))
    flat = {"/".join(str(k.key) for k in kp): v for kp, v in
            jax.tree_util.tree_flatten_with_path(params)[0]}
    for path, leaf in lay.items():
        layers = range(leaf.layers) if leaf.layers else [None]
        for i in layers:
            got = weights.layer_leaf(lay, path, words, i)
            want = flat[path] if i is None else flat[path][i]
            assert np.array_equal(np.asarray(got), np.asarray(want)), path


def test_seeds_past_32_bits_differ():
    _, lay, _ = setup(*CASES[0])
    a = weights.make(lay, 5)["embed"]
    b = weights.make(lay, SEED)["embed"]
    assert not np.array_equal(np.asarray(a), np.asarray(b))


def test_dense_loss_and_gradient_match_the_program():
    mod, c = CASES[0]
    cfg, lay, params = setup(mod, c)
    t = tokens(c["vocab_size"], (2, 16))
    labels = jnp.roll(t, -1, axis=1)
    loss, grads = mod.loss_and_grad(c, params, t, labels)
    (p_loss, _), p_grads = jax.value_and_grad(
        lambda p: lm.train_loss(cfg, p, {"tokens": t, "labels": labels},
                                remat="none"), has_aux=True)(params)
    assert float(loss) == pytest.approx(float(p_loss), rel=1e-6)
    for (kp, g), pg in zip(jax.tree_util.tree_flatten_with_path(grads)[0],
                           jax.tree.leaves(p_grads)):
        scale = float(jnp.abs(pg).max())
        assert float(jnp.abs(g - pg).max()) <= 1e-5 * max(scale, 1e-3), kp


def test_layout_mismatch_is_refused():
    mod, c = CASES[0]
    cfg = ModelConfig(**mod.program_config(c))
    wrong = mod.layout(dict(c, intermediate_size=c["intermediate_size"] + 1))
    with pytest.raises(ValueError, match="differs"):
        weights.check_layout(wrong, lm.abstract_params(cfg))
