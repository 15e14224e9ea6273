"""The work each configuration declares, and that every cell resolves by name."""
import json
import os
import sys
from types import SimpleNamespace

import jax
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
from bench import harness  # noqa: E402

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    SPEC = json.load(f)
CELLS = [w["name"] for w in SPEC["workloads"]]


def _config(name):
    """A configuration file and its model module, found by name as the
    harness finds them."""
    with open(os.path.join(ROOT, "bench", "configs", f"{name}.json")) as f:
        config = json.load(f)
    model = harness._load_module(
        os.path.join(ROOT, "bench", "configs", f"{config['model']}.py"), "model")
    return SimpleNamespace(config=config, model=model)


def _shapes(cell, width=1.0):
    return jax.eval_shape(lambda k: cell.model.build(cell.config, k, width),
                          jax.random.PRNGKey(0))


def _independent_macs(params, size: int, batch: int) -> int:
    """Multiply-adds from the parameter shapes alone, walked in the order of
    the bottleneck blocks: 7x7/2 stem, /2 max pool, the stride on each later
    stage's first 1x1."""
    out = size // 2
    macs = out * out * int(np.prod(params["conv1"].shape))
    out = out // 2
    for name in sorted((k for k in params if "_b" in k),
                       key=lambda n: (n.split("_b")[0], int(n.split("_b")[1]))):
        blk = params[name]
        stride = 2 if name.endswith("_b0") and not name.startswith("conv2") else 1
        out //= stride
        for conv in ("proj", "c1", "c2", "c3"):
            if conv in blk:
                macs += out * out * int(np.prod(blk[conv].shape))
    macs += int(np.prod(params["fc"]["w"].shape))
    return batch * macs


def test_dense_resnet50_is_3_8e9_multiply_adds_per_image():
    cell = _config("resnet50_dense")
    params = _shapes(cell)
    macs = sum(layer["flops"] for layer in cell.model.work(cell.config, params, 1)) / 2
    assert 3.8e9 <= macs < 3.9e9   # He et al., Table 1: 3.8 x 10^9
    kinds = [layer["kind"] for layer in cell.model.work(cell.config, params, 1)]
    assert [kinds.count(k) for k in ("stem", "conv3x3", "conv1x1", "fc")] == [1, 16, 36, 1]


@pytest.mark.parametrize("config", ["resnet50_dense", "resnet50_pruned50"])
@pytest.mark.parametrize("batch", [1, 32])
def test_work_equals_an_independent_count_from_the_parameter_shapes(config, batch):
    cell = _config(config)
    params = _shapes(cell)
    layers = cell.model.work(cell.config, params, batch)
    assert sum(layer["flops"] for layer in layers) == \
        2 * _independent_macs(params, cell.config["image_size"], batch)


def test_pruned_work_is_the_pruned_networks():
    dense, pruned = _config("resnet50_dense"), _config("resnet50_pruned50")
    d = {x["name"]: x for x in dense.model.work(dense.config, _shapes(dense), 1)}
    p = {x["name"]: x for x in pruned.model.work(pruned.config, _shapes(pruned), 1)}
    assert d.keys() == p.keys()
    assert p["conv2_b0_3x3"]["flops"] * 4 == d["conv2_b0_3x3"]["flops"]
    assert p["conv2_b0_1x1a"]["flops"] * 2 == d["conv2_b0_1x1a"]["flops"]
    assert p["conv2_b0_proj"] == d["conv2_b0_proj"]
    assert p["conv1"] == d["conv1"]
    ratio = sum(x["flops"] for x in p.values()) / sum(x["flops"] for x in d.values())
    assert 0.43 < ratio < 0.45


def test_bytes_count_input_weights_output_and_epilogue_once():
    cell = _config("resnet50_dense")
    layers = {x["name"]: x for x in cell.model.work(cell.config, _shapes(cell), 2)}
    # conv2_b0_1x1b: 56x56x64 -> 56x56x256, shortcut added in its epilogue
    act_in, act_out, w = 56 * 56 * 64, 56 * 56 * 256, 64 * 256
    assert layers["conv2_b0_1x1b"]["bytes"] == 4 * (
        2 * act_in + w + 2 * act_out + 2 * 256 + 2 * act_out)
    # conv3_b0_1x1a reads every other pixel of its 56x56x256 input
    assert layers["conv3_b0_1x1a"]["bytes"] == 4 * (
        2 * 28 * 28 * 256 + 256 * 128 + 2 * 28 * 28 * 128 + 2 * 128)


def test_pruning_matches_the_programs_resnet50_prune():
    """The benchmark prunes with its own code; it keeps the channels that the
    program's ``resnet50_prune`` keeps, in the same order."""
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.models.cnn import resnet50_prune
    dense, pruned = _config("resnet50_dense"), _config("resnet50_pruned50")
    key = jax.random.PRNGKey(3)
    ours = pruned.model.build(pruned.config, key, 1 / 16)
    theirs, _ = resnet50_prune(dense.model.build(dense.config, key, 1 / 16), 0.5)
    flat_ours = jax.tree_util.tree_leaves_with_path(ours)
    flat_theirs = dict(jax.tree_util.tree_leaves_with_path(theirs))
    assert len(flat_ours) == len(flat_theirs)
    for path, leaf in flat_ours:
        np.testing.assert_array_equal(np.asarray(leaf), np.asarray(flat_theirs[path]))


@pytest.mark.parametrize("workload", CELLS)
def test_each_cell_resolves_its_files_by_name(workload):
    cell = harness.load_cell(workload)
    w = {x["name"]: x for x in SPEC["workloads"]}[workload]
    assert cell.config_name == w["config"] and cell.chips == w["chips"]
    assert os.path.isfile(os.path.join(ROOT, "bench", "traffic", f"{w['traffic']}.json"))
    assert {m["name"] for m in cell.per_layer} == set(cell.readers)
    assert all(callable(r.read) for r in cell.readers.values())
    assert "setup_s" in {m["name"] for m in cell.end_to_end}
    peaks = harness.peaks_for("TPU v5 lite", cell.config["dtype"])
    assert peaks.flops == 197e12 and peaks.hbm == 819e9


def test_every_config_file_is_used_and_unknown_devices_are_refused():
    used = {w["config"] for w in SPEC["workloads"]}
    assert used == {c["name"] for c in SPEC["configs"]}
    for c in SPEC["configs"]:
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
    with pytest.raises(KeyError):
        harness.peaks_for("cpu", "float32")


def test_seeds_past_32_bits_stay_distinct():
    keys = [np.asarray(harness.seed_key(s)) for s in (7, 2**32 + 7, 2**33 + 7)]
    assert len({k.tobytes() for k in keys}) == 3
