"""A configuration brings its own reference: what must not move for the
dense configurations when one does, and the pieces a sparse-expert one
needs from the harness.

* the model FLOPs per token that ``mfu`` divides by;
* the leaf names that draw the weights (``weights.draw`` indexes the
  sorted names, so a changed name changes every weight);
* a model of several layer segments: each segment a stacked prefix of its
  own, split per layer by the comparison;
* a nested configuration block reaching the program as its dataclass.
"""
import json

import jax
import numpy as np
import pytest

import cells
import compare
import harness
import refmodel
import weights

TEST_DATA = cells.BENCH / "tests" / "data" / "BENCHMARK.json"
DENSE = {
    "qwen2-0.5b.1chip.plain": [
        "embed/table", "final_norm/scale", "layers/attn/bk",
        "layers/attn/bq", "layers/attn/bv", "layers/attn/wk",
        "layers/attn/wo", "layers/attn/wq", "layers/attn/wv",
        "layers/mlp/w1", "layers/mlp/w2", "layers/mlp/w3",
        "layers/norm1/scale", "layers/norm2/scale"],
    "gpt-6.7b.tp4.taco": [
        "embed/table", "final_norm/bias", "final_norm/scale", "head/table",
        "layers/attn/bk", "layers/attn/bq", "layers/attn/bv",
        "layers/attn/wk", "layers/attn/wo", "layers/attn/wq",
        "layers/attn/wv", "layers/mlp/b1", "layers/mlp/b2", "layers/mlp/w1",
        "layers/mlp/w2", "layers/norm1/bias", "layers/norm1/scale",
        "layers/norm2/bias", "layers/norm2/scale", "pos_embed"],
}


@pytest.fixture(autouse=True)
def program_on_path(monkeypatch):
    monkeypatch.syspath_prepend(str(cells.ROOT / "src"))


@pytest.mark.parametrize("config,seq,flops", [
    ("qwen2-0.5b", 4096, 4_020_731_904),
    ("gpt-6.7b-l8", 2048, 11_727_273_984)])
def test_dense_flops_per_token_are_unchanged(config, seq, flops):
    with open(cells.BENCH / "configs" / f"{config}.json") as f:
        arch = json.load(f)["arch"]
    assert refmodel.flops_per_token(arch, seq) == flops


@pytest.mark.parametrize("workload", sorted(DENSE))
def test_real_configurations_keep_their_reference_and_leaf_names(workload):
    from repro.configs.base import ArchConfig, make_plan
    from repro.models.model import Model

    cell = cells.load(workload)
    assert cell.reference is refmodel
    cfg = harness.program_config(ArchConfig, cell.arch)
    model = Model(cfg, make_plan(cfg, cell.tp, 1))
    flat, _ = jax.tree_util.tree_flatten_with_path(model.abstract_params())
    got = {weights.program_name(p): tuple(a.shape) for p, a in flat}
    assert sorted(got) == DENSE[workload]
    pos = got.get("pos_embed", (0,))[0]
    assert got == refmodel.shapes(cell.arch, pos)


def _path(*keys):
    return tuple(jax.tree_util.DictKey(k) if isinstance(k, str)
                 else jax.tree_util.SequenceKey(k) for k in keys)


def test_each_layer_segment_is_a_stacked_prefix_of_its_own():
    names = [weights.program_name(_path(*p)) for p in [
        ("segments", 0, "mlp", "w1"), ("segments", 1, "moe", "w1"),
        ("segments", 12, "attn", "wq"), ("enc_segments", 0, "attn", "wq"),
        ("embed", "table")]]
    assert names == ["layers/mlp/w1", "layers1/moe/w1", "layers12/attn/wq",
                     "enc_segments/0/attn/wq", "embed/table"]
    assert [weights.stacked(n) for n in names] == [True, True, True, False,
                                                   False]


def test_every_stacked_segment_is_compared_per_layer():
    shapes = {"layers/mlp/w1": (1, 4, 6), "layers1/moe/w1": (3, 2, 4, 6),
              "embed/table": (10, 4)}
    idx = compare.sample_index(shapes, 7)
    assert idx["layers/mlp/w1"].shape == (1, compare.SAMPLE)
    assert idx["layers1/moe/w1"].shape == (3, compare.SAMPLE)
    assert idx["embed/table"].shape == (compare.SAMPLE,)
    assert idx["layers1/moe/w1"].max() < 2 * 4 * 6

    rng = np.random.default_rng(0)
    tree = {k: rng.normal(size=s).astype(np.float32)
            for k, s in shapes.items()}
    sample = compare.take_sample(tree, idx)
    assert sample["layers1/moe/w1"].shape == (3, compare.SAMPLE)
    norms = refmodel.leaf_norms(tree)
    assert sorted(compare.flat(norms)) == [
        "embed/table", "layers/mlp/w1.0", "layers1/moe/w1.0",
        "layers1/moe/w1.1", "layers1/moe/w1.2"]
    assert compare.flat(norms)["layers1/moe/w1.2"] == pytest.approx(
        float(np.linalg.norm(tree["layers1/moe/w1"][2])), rel=1e-6)


def test_a_nested_block_reaches_the_program_as_its_dataclass():
    from repro.configs.base import ArchConfig, MoeConfig

    cell = cells.load("tiny.1dev.moe", TEST_DATA)
    assert cell.reference.__name__ == "refmodel_tiny_moe"
    cfg = harness.program_config(ArchConfig, cell.arch)
    assert cfg.moe == MoeConfig(n_experts=4, top_k=2, capacity_factor=2.0)


@pytest.mark.parametrize("change,missing", [
    ({"moe": {"n_experts": 4, "top_k": 2, "n_shared_experts": 2}},
     "n_shared_experts"),
    ({"kv_lora_rank": 512}, "kv_lora_rank")])
def test_a_key_the_program_lacks_ends_the_run(change, missing):
    from repro.configs.base import ArchConfig

    arch = dict(cells.load("tiny.1dev.moe", TEST_DATA).arch, **change)
    with pytest.raises(SystemExit, match=missing):
        harness.program_config(ArchConfig, arch)
