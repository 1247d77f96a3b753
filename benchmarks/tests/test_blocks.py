"""A block of layers is a file (``blocks/<block>.py``): the dense block
through the plug gives the bytes and the costs the benchmark gave before
there was one (pinned against the parent commit of PR 27, whose values and
digests stand below), an unknown block is an error, and a second kind of
block (``data/blocks/toy_routed.py``: 3-D expert tensors, an F32 router,
two metadata keys of its own) is written through ``run.py``'s own path,
read back by the program's reader and costed by its own functions."""

import hashlib
import json
import os
import shutil

import pytest

import costs
import ggufgen
import run as bench_run
from conftest import BENCH, HERE, ROOT
from test_ggufgen import tiny_cfg
from test_timeline_readers import reader, run_of, span

# sha256 of the file the parent's ``ggufgen.write_gguf`` writes
PARENT_FILES = {
    "Q4_K/Q6_K": "08d14695cc348167d2d969cf1e5209d2c03fee22cb2bdb543ed1925ae5270217",
    "Q8_0/F16": "5414a54fa7d8aaa810f05d3555aed8ce351d50016cb13f31875464cc162ab2be",
    "Q5_K/Q8_0": "6ac30ce6a1f3ae74de401ab92c0a54f6c6fb10bbe6c9ddccc9b0458d382ffbeb",
    "window+head_dim": "07550586dff1cf70f01e1b095e20e605137219ce1c9bcb3d1129294ebf8d13bc",
}

# the parent's ``costs.*`` at published shapes, and its cache file's name
PARENT = {
    "mistral-7b-v0.2-q4km-8lane": {
        "file": "mistral-7b-v0.2-q4km-8lane-10192c4f26de.gguf",
        "weight_bytes_per_step": 4553498624, "kv_bytes_per_token": 131072,
        "linear_params": 7110393856, "decode_step_bytes": 5043511296.0,
        "decode_step_flops": 115726090240.0,
        "prefill_flops": 35741973348352.0, "decode_step_bytes_kv1": 4585979904.0},
    "solar-10.7b-v1-q4km-serial": {
        "file": "solar-10.7b-v1-q4km-serial-6fdc3d74c62f.gguf",
        "weight_bytes_per_step": 6776479744, "kv_bytes_per_token": 196608,
        "linear_params": 10600054784, "decode_step_bytes": 7511465984.0,
        "decode_step_flops": 172540559360.0,
        "prefill_flops": 53612828950528.0, "decode_step_bytes_kv1": 6825197568.0},
}

COSTS = {
    "weight_bytes_per_step": costs.weight_bytes_per_step,
    "kv_bytes_per_token": costs.kv_bytes_per_token,
    "linear_params": costs.linear_params,
    "decode_step_bytes": lambda c: costs.decode_step_bytes(c, 8, 467.25),
    "decode_step_flops": lambda c: costs.decode_step_flops(c, 8, 467.25),
    "prefill_flops": lambda c: costs.prefill_flops(c, 2448),
    "decode_step_bytes_kv1": lambda c: costs.decode_step_bytes(c, 1, 495.5, 1),
}


def published(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def toy_cfg():
    with open(os.path.join(HERE, "data", "toy-routed.json")) as f:
        return json.load(f)


@pytest.fixture
def toy_blocks(monkeypatch):
    """The test's own means of adding a block: its own directory."""
    monkeypatch.setattr(ggufgen, "BLOCK_DIR",
                        os.path.join(HERE, "data", "blocks"))
    mod = ggufgen.block_of(toy_cfg())
    del mod.CALLS[:]
    return mod


# -- the dense block through the plug ------------------------------------------

def _dense_case(case):
    if case == "window+head_dim":
        cfg = tiny_cfg()
        cfg.update(sliding_window=64, head_dim=32)
        return cfg
    return tiny_cfg(*case.split("/"))


@pytest.mark.parametrize("case", sorted(PARENT_FILES))
def test_a_dense_file_has_the_parents_bytes(case, tmp_path):
    path = str(tmp_path / "t.gguf")
    ggufgen.write_gguf(_dense_case(case), path)
    with open(path, "rb") as f:
        assert hashlib.sha256(f.read()).hexdigest() == PARENT_FILES[case]


@pytest.mark.parametrize("what", sorted(COSTS))
@pytest.mark.parametrize("name", sorted(PARENT))
def test_published_costs_are_the_parents(name, what):
    got = COSTS[what](published(name))
    assert got == PARENT[name][what] and type(got) is type(PARENT[name][what])


@pytest.mark.parametrize("name", sorted(PARENT))
def test_the_cache_file_keeps_its_name(name, tmp_path, monkeypatch):
    monkeypatch.setattr(bench_run, "CACHE", str(tmp_path))
    monkeypatch.setattr(ggufgen, "write_gguf",
                        lambda cfg, path: open(path, "w").close() or 0)
    cfg = published(name)
    assert "block" not in cfg
    assert os.path.basename(bench_run.ensure_gguf(cfg)) == PARENT[name]["file"]
    # the same sizes under a named block are another file
    other = os.path.basename(bench_run.ensure_gguf({**cfg, "block": "dense"}))
    assert other != PARENT[name]["file"]


def test_absent_block_is_dense():
    cfg = tiny_cfg()
    assert ggufgen.block_of(cfg) is ggufgen.block_of({**cfg, "block": "dense"})
    assert ggufgen.block_of(cfg).__file__ == \
        os.path.join(BENCH, "blocks", "dense.py")


@pytest.mark.parametrize("call", [
    lambda c, p: ggufgen.tensor_plan(c),
    lambda c, p: ggufgen.write_gguf(c, p),
    lambda c, p: costs.weight_bytes_per_step(c),
    lambda c, p: costs.decode_step_bytes(c, 1, 100),
    lambda c, p: costs.decode_step_flops(c, 1, 100),
    lambda c, p: costs.prefill_flops(c, 100),
], ids=["tensor_plan", "write_gguf", "weight_bytes", "decode_step_bytes",
        "decode_step_flops", "prefill_flops"])
def test_an_unknown_block_is_an_error_never_a_default(call, tmp_path):
    cfg = {**tiny_cfg(), "block": "routed_not_here"}
    with pytest.raises(KeyError, match="routed_not_here"):
        call(cfg, str(tmp_path / "t.gguf"))
    assert not os.listdir(tmp_path)


def test_run_py_and_the_readers_name_no_block():
    """Shapes live in ``blocks/`` alone: nothing else spells a tensor."""
    files = [os.path.join(BENCH, f) for f in
             ("run.py", "traffic.py", "costs.py", "ggufgen.py")]
    lm = os.path.join(BENCH, "layer_metrics")
    files += [os.path.join(lm, f) for f in os.listdir(lm) if f.endswith(".py")]
    for path in files:
        with open(path) as f:
            text = f.read()
        for word in ("ffn_gate", "attn_q", "attn_output", "_exps", "blk."):
            assert word not in text, (path, word)


# -- a second kind of block, as new files only -----------------------------------

def test_toy_routed_block_reads_back_through_the_programs_reader(
        toy_blocks, tmp_path, monkeypatch):
    from llama_fastapi_k8s_gpu_tpu.gguf import GGMLType, GGUFFile

    monkeypatch.setattr(bench_run, "CACHE", str(tmp_path))
    cfg = toy_cfg()
    path = bench_run.ensure_gguf(cfg)          # run.py's own path to a file
    assert os.path.basename(path).startswith("toy-routed-")
    assert bench_run.ensure_gguf(cfg) == path  # found again, not rewritten
    gf = GGUFFile(path)
    assert gf.architecture == "toymoe"
    assert gf.hparam("expert_count") == 8
    assert gf.hparam("expert_used_count") == 2
    assert gf.hparam("block_count") == 2       # the shared keys are there
    experts = gf["blk.1.ffn_down_exps.weight"]
    assert tuple(reversed(experts.shape)) == (8, 256, 256)   # 3-D, ggml order
    assert experts.ggml_type == GGMLType.Q6_K
    assert experts.astype_f32().shape == (8, 256, 256)
    router = gf["blk.0.ffn_gate_inp.weight"]
    assert router.ggml_type == GGMLType.F32
    assert tuple(reversed(router.shape)) == (8, 256)
    assert abs(float(router.astype_f32().std()) - 256 ** -0.5) < 0.01
    plan = ggufgen.tensor_plan(cfg)
    assert set(gf.tensors) == {name for name, _, _ in plan}
    assert len(plan) == 10 * 2 + 3
    assert "blk.0.ffn_gate.weight" not in gf   # no dense feed-forward
    # every tensor lies inside the file, the last one to its end
    assert gf.data_offset + max(t.offset + t.nbytes
                                for t in gf.tensors.values()) \
        <= os.path.getsize(path)


def test_toy_routed_costs_by_hand(toy_blocks):
    cfg = toy_cfg()
    d = f = 256
    attn_b = 3 * d * d * 144 // 256 + d * d * 210 // 256     # q, k, o; v
    router_b, norms_b = 8 * d * 4, 2 * d * 4
    expert_b = 2 * f * d * 144 // 256 + d * f * 210 // 256
    head_b = 19000 * d * 210 // 256 + d * 4
    rest = 2 * (attn_b + router_b + norms_b) + head_b
    kv = 2 * 2 * d * 2
    # no run: every expert the lanes could have picked, 1 lane x 2, 8 lanes -> all 8
    assert costs.decode_step_bytes(cfg, 1, 100) == \
        rest + 2 * 2 * expert_b + 100 * kv + d * 2
    assert costs.decode_step_bytes(cfg, 8, 100) == \
        rest + 2 * 8 * expert_b + 8 * 100 * kv + 8 * d * 2
    # a run with the program's counters: 300 expert reads in 50 steps of 2 layers
    a = "router_experts_read 100\nrouter_steps 10\n"
    b = "router_experts_read 400\nrouter_steps 60\n"
    run = run_of(samples=[(0.0, a), (1.0, b)])
    assert costs.decode_step_bytes(cfg, 8, 100, run=run) == \
        rest + 2 * 3.0 * expert_b + 8 * 100 * kv + 8 * d * 2
    per_token = 2 * (4 * d * d + 8 * d) + 19000 * d + 2 * 2 * 3 * f * d
    assert costs.decode_step_flops(cfg, 2, 0) == 2 * 2 * per_token
    assert costs.prefill_flops(cfg, 1) == pytest.approx(
        2.0 * (per_token - 19000 * d) + 2.0 * 19000 * d + 2 * d * 2)


def test_the_roofline_reader_calls_the_runs_own_block(toy_blocks, monkeypatch):
    """``decode_step_roofline`` on a run of the toy configuration: the
    block's cost functions are called, with the run, and the dense ones
    would have given another number."""
    cfg = toy_cfg()
    chunks = [span("decode_chunk", 1.0 + i, 1.1 + i, {"tokens": 1 + 8 * (i + 1)})
              for i in range(3)]
    trace = {"root": span("request", 0.0, 5.0, children=[
        span("prefill", 0.0, 1.0, {"n_prompt": 60})] + chunks)}
    a = "router_experts_read 0\nrouter_steps 0\n"
    b = "router_experts_read 600\nrouter_steps 100\n"
    run = run_of(
        traces=[trace], samples=[(0.0, a), (1.0, b)], config=cfg,
        kernel_groups={"decode_program": ["generate_chunk"]},
        device={"kind": "TPU v5 lite"},
        profile={"modules": [("jit_generate_chunk_jit(1)", 0.0, 0.008)]})
    got = reader("decode_step_roofline")(run)
    assert toy_blocks.CALLS == [("decode_step_flops", True),
                                ("decode_step_bytes", True)]
    note = run["notes"]["decode_step_roofline"]
    assert note["lanes"] == 2 and note["device_step_ms"] == pytest.approx(1.0)
    least = costs.decode_step_bytes(cfg, 2, 60, run=run) / 819e9
    assert got == pytest.approx(100.0 * least / 0.001)
    dense = {k: v for k, v in cfg.items() if k != "block"}
    dense["gguf"] = {**cfg["gguf"], "tensor_types": {
        **cfg["gguf"]["tensor_types"], "ffn_gate": "Q4_K", "ffn_up": "Q4_K",
        "ffn_down": "Q6_K"}}
    routed = costs.decode_step_bytes(cfg, 2, 60, run=run)
    monkeypatch.setattr(ggufgen, "BLOCK_DIR", os.path.join(BENCH, "blocks"))
    assert costs.decode_step_bytes(dense, 2, 60) != routed


# -- a rehearsal cell is one new file ---------------------------------------------

def test_a_rehearsal_cell_of_its_own_file(tmp_path, monkeypatch):
    root = tmp_path / "co"
    shutil.copytree(os.path.join(BENCH, "rehearsal"),
                    root / "benchmarks" / "rehearsal")
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root / "BENCHMARK.json")
    cells = root / "benchmarks" / "rehearsal" / "cells"
    cells.mkdir()
    (cells / "rehearsal.mine.json").write_text(json.dumps({
        "name": "rehearsal.mine", "config": "tiny-q8-serial",
        "traffic": "tiny-closed-1", "chips": 1, "why": "a cell in one file"}))
    (cells / "rehearsal.other.json").write_text(json.dumps({
        "name": "not-the-file's-name", "config": "tiny-q8-serial",
        "traffic": "tiny-closed-1", "chips": 1, "why": "-"}))
    monkeypatch.setattr(bench_run, "ROOT", str(root))
    monkeypatch.setattr(bench_run, "HERE", str(root / "benchmarks"))
    got = bench_run.find_cell("rehearsal.mine")
    assert got["rehearsal"] and got["config"]["name"] == "tiny-q8-serial"
    assert got["mix"]["loop"] == "closed" and len(got["per_layer"]) >= 26
    assert bench_run.find_cell("rehearsal.serial")["rehearsal"]   # cells.json
    for missing in ("rehearsal.other", "rehearsal.nowhere"):
        with pytest.raises(SystemExit):
            bench_run.find_cell(missing)
