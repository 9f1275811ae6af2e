"""granite-4.0-h's layer pattern against its plain float32 reference, at a
tiny size on the CPU: the program's forward, prefill and decode through
the paged cache of ``ServeEngine`` (a recycled slot included), the weight
layout, the registry's description of the model, the operation and byte
counts, and whole runs of a tiny cell.

Tolerances: the float32 program and the float32 reference differ only in
the order of sums. The reference runs the SSD recurrence token by token
where the program's forward runs it in chunks, and it blocks attention
over queries where the engine attends over a paged pool chunk by chunk.
So logits agree to a few float32 ulps of their size, ~1e-6 here: 1e-5
leaves room. The bf16 program's tiny cell is held to the limit in
``LIMIT``, between its own gap and the float8 control's."""

import dataclasses
import json
import os
import shutil

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import run, weights
from chipbench.costs import granite_hybrid as costs
from chipbench.reference import granite_hybrid as ref
from chipbench.tests import tiny
from repro.configs.registry import get_config
from repro.models import lm
from repro.models.config import ModelConfig
from repro.serve import ServeEngine

# attention third of four layers: neither first nor last
GRANITE = dict(
    name="tiny-granite", family="granite_hybrid", num_hidden_layers=4,
    layer_types=["mamba", "mamba", "attention", "mamba"], hidden_size=64,
    intermediate_size=128, shared_intermediate_size=128,
    num_attention_heads=4, num_key_value_heads=2, vocab_size=256,
    mamba_expand=2, mamba_n_heads=8, mamba_d_head=16, mamba_d_state=16,
    mamba_d_conv=4, mamba_n_groups=1, mamba_chunk_size=8,
    mamba_conv_bias=True, mamba_proj_bias=False, attention_bias=False,
    hidden_act="silu", normalization_function="rmsnorm", num_local_experts=0,
    position_embedding_type="nope", rope_theta=10000, rms_norm_eps=1e-5,
    tie_word_embeddings=True, embedding_multiplier=12,
    attention_multiplier=0.0625, residual_multiplier=0.22, logits_scaling=8,
    torch_dtype="bfloat16")
F32 = dict(GRANITE, torch_dtype="float32")
SEED = 2**33 + 5            # past 32 bits: the key keeps both words
TOL = 1e-5

# Widest logit gaps of the tiny cell on the CPU over seeds 1-8, 2**31+3
# and 2**33+9 (0.5 s of fill, 1.5 s windows, 8 requests compared): the
# bf16 program reads at most 0.0011, the float8 control at least 0.0045
# (the logits are divided by logits_scaling 8). The limit lies between.
LIMIT = 0.002


def setup(c):
    cfg = ModelConfig(**ref.program_config(c))
    lay = ref.layout(c)
    weights.check_layout(lay, lm.abstract_params(cfg))
    return cfg, lay, weights.make(lay, SEED)


def getter(lay, seed=SEED):
    words = jnp.asarray(weights.seed_words(seed))

    def get(path, layer):
        return weights.layer_leaf(lay, path, words, layer).astype(jnp.float32)
    return get


def test_reference_logits_match_the_program():
    cfg, lay, params = setup(F32)
    t = jnp.asarray(np.random.default_rng(0).integers(0, 256, (2, 24)),
                    jnp.int32)                  # 24 tokens: three SSD chunks
    prog = lm.forward(cfg, params, {"tokens": t}, remat="none")[0]
    want = jax.jit(lambda x: ref.logits(F32, getter(lay), x))(t)
    scale = float(jnp.abs(want).max())
    assert scale > 0.01
    assert float(jnp.abs(prog - want).max()) < TOL * max(1.0, scale)


def test_layer_by_layer_weights_equal_the_whole_tree():
    _, lay, params = setup(GRANITE)
    words = jnp.asarray(weights.seed_words(SEED))
    flat = {"/".join(str(k.key) for k in kp): v for kp, v in
            jax.tree_util.tree_flatten_with_path(params)[0]}
    assert {p.split("/")[1] for p in flat if p.startswith("layers/")} == {
        "mamba", "attention"}
    for path, leaf in lay.items():
        for i in (range(leaf.layers) if leaf.layers else [None]):
            got = weights.layer_leaf(lay, path, words, i)
            want = flat[path] if i is None else flat[path][i]
            assert np.array_equal(np.asarray(got), np.asarray(want)), path


def test_published_layout_is_the_programs_tree():
    c = json.load(open(os.path.join(tiny.REPO, "chipbench", "configs",
                                    "granite-4.0-h-micro.json")))
    cfg = ModelConfig(**ref.program_config(c))
    weights.check_layout(ref.layout(c), lm.abstract_params(cfg))
    assert ref.layout(c)["layers/mamba/ssd/w_in_x"].layers == 36
    assert ref.layout(c)["layers/attention/attn/wq"].layers == 4


def test_registry_config_is_the_benchmark_config():
    """One description of the model: the registry's ModelConfig equals
    the one chipbench builds from its configuration file, field for
    field."""
    c = json.load(open(os.path.join(tiny.REPO, "chipbench", "configs",
                                    "granite-4.0-h-micro.json")))
    want = dataclasses.asdict(ModelConfig(**ref.program_config(c)))
    got = dataclasses.asdict(get_config("granite-4.0-h-micro"))
    assert {k: (got[k], want[k]) for k in want if got[k] != want[k]} == {}


class Recording(ServeEngine):
    """Keeps, per request, each float32 logits row it sampled from."""

    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        self.sampled = {}

    def _finish(self, slots_live, adv, rows_np):
        for i in slots_live:
            r = self.active[i]
            if self._fed[i] + int(adv[i]) >= len(r.prompt):
                self.sampled.setdefault(r.uid, []).append(rows_np[i].copy())
        super()._finish(slots_live, adv, rows_np)


def test_paged_prefill_and_decode_match_the_reference():
    """Chunked prefill beside decode through the paged cache, then decode,
    give the reference's full-forward logits at every sampled position;
    the third request runs in a recycled slot, so the SSD reset and the
    zero-epoch each act on their own layers."""
    cfg, lay, params = setup(F32)
    eng = Recording(cfg, params, batch_slots=2, max_len=64, prefill_chunk=4)
    reqs = [eng.submit(list(range(3, 14)), max_new_tokens=6)]
    eng.step()
    reqs.append(eng.submit([8, 1, 4, 4, 2, 6, 200, 17, 9], max_new_tokens=12))
    reqs.append(eng.submit([7, 3, 9, 250, 11], max_new_tokens=5))
    eng.run()
    assert [r.done for r in reqs] == [True] * 3
    assert eng.kv.epoch.sum() == 3          # one slot served two requests
    fwd = jax.jit(lambda x: ref.logits(F32, getter(lay), x))
    for r in reqs:
        seq = r.prompt + r.generated[:-1]
        want = np.asarray(fwd(jnp.asarray([seq], jnp.int32)))[0]
        want = want[len(r.prompt) - 1:]
        got = np.stack(eng.sampled[r.uid])
        assert got.shape == want.shape
        scale = float(np.abs(want).max())
        assert float(np.abs(got - want).max()) < TOL * max(1.0, scale), r.uid


def test_costs_by_hand():
    c = dict(GRANITE, hidden_size=8, shared_intermediate_size=16,
             num_attention_heads=4, num_key_value_heads=2, mamba_n_heads=4,
             mamba_d_head=4, mamba_d_state=3, vocab_size=10)
    # d 8, di 16, N 3, H 4, P 4, F 16; head_dim 2
    # mamba: 8*(32+6+4) + 16*8 = 464, mlp 3*8*16 = 384 -> 848, three layers
    # attention: q 8*4*2=64, k,v 2*8*2*2=64, o 64, mlp 384 -> 576, one layer
    # tied head 8*10 = 80
    assert costs.matmul_weights(c) == 3 * 848 + 576 + 80
    # state f32 4*3*4*4 = 192 B, conv window 3*(16+6) bf16 = 132 B; 3 layers
    assert costs.state_bytes_per_slot(c) == 3 * (192 + 132)
    # k and v of one attention layer, 2 kv heads of 2, bf16
    assert costs.kv_bytes_per_token(c) == 16
    # per mamba layer 5*4*3*4 + 2*4*22 = 416; attention 4*4*2 = 32 a key
    base = 2 * costs.matmul_weights(c) + 3 * 416
    assert costs.token_flops(c, 5) == base + 32 * 5
    # slot 0: 5 resident, decodes 1 (sees 6 keys); slot 1: fresh, feeds 2
    flops, nbytes = costs.tick(c, [5, 0, 7], [1, 2, 0])
    assert flops == 3 * base + 32 * (6 + 1 + 2)
    assert nbytes == (costs.weight_bytes(c) + 2 * 2 * 3 * 324
                      + (5 + 0 + 3) * 16)
    # weights: bf16 (3200 matmul + 8 final norm) + per mamba layer bf16
    # (2*8 + 16 + 5*22) and three f32 vectors of 4, per attention layer
    # two norms of 8
    assert costs.weight_bytes(c) == (2 * 3208 + 3 * (2 * 142 + 48)
                                     + 2 * 16)


def test_a_layout_sized_for_every_layer_is_refused():
    cfg, _, _ = setup(GRANITE)
    uniform = ref.layout(dict(GRANITE, layer_types=["attention"] * 4))
    with pytest.raises(ValueError, match="differs"):
        weights.check_layout(uniform, lm.abstract_params(cfg))


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A benchmark root with the tiny granite cell, built from new files."""
    tmp = str(tmp_path_factory.mktemp("bench"))
    os.makedirs(os.path.join(tmp, "chipbench", "configs"))
    os.makedirs(os.path.join(tmp, "chipbench", "traffic"))
    shutil.copytree(os.path.join(tiny.REPO, "chipbench", "metrics"),
                    os.path.join(tmp, "chipbench", "metrics"))
    with open(os.path.join(tiny.REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    path = os.path.join("chipbench", "configs", "tiny-granite.json")
    with open(os.path.join(tmp, path), "w") as f:
        json.dump(dict(GRANITE, reduced=[]), f)
    with open(os.path.join(tmp, "chipbench", "traffic",
                           "tiny-granite.chat.json"), "w") as f:
        json.dump(tiny.traffic(LIMIT), f)
    bench["configs"] = [{"name": "tiny-granite", "source": "test",
                         "file": path, "reduced": [], "why": "test"}]
    bench["workloads"] = [{"name": "tiny-granite.chat",
                           "config": "tiny-granite",
                           "traffic": "tiny-granite.chat", "chips": 1,
                           "why": "test"}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = ["tiny-granite.chat"]
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return tmp


@pytest.mark.parametrize("control", [False, True], ids=["program", "control"])
def test_a_run_of_the_tiny_cell(root, control):
    """The bf16 program's run is correct; the float8 control in its place
    is not, at the same limit."""
    line = run.run_cell(root, "tiny-granite.chat", 2**31 + 3, 1.5, False,
                        require_chip=False, peaks=tiny.PEAKS, control=control)
    assert line["correct"] is (not control)
    assert line["failed"] == 0 and line["compiles_in_window"] == 0
    assert line["checks"]["requests_compared"]["value"] == 8
