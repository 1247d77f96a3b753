"""The bench entry points must produce one valid JSON line on the tiny CPU
preset, and must refuse — no result line, non-zero exit — to run a full-size
preset where JAX finds no TPU."""

from __future__ import annotations

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(script, extra_env=None, timeout=900):
    env = dict(os.environ, JAX_PLATFORMS="cpu", LFKT_BENCH_PRESET="tiny",
               **(extra_env or {}))
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, script)],
        env=env, capture_output=True, text=True, timeout=timeout)
    lines = [ln for ln in out.stdout.splitlines() if ln.strip()]
    assert lines, out.stderr[-2000:]
    parsed = json.loads(lines[-1])
    assert "metric" in parsed and "value" in parsed, parsed
    # every emitted line is provenance-stamped (utils/provenance.py)
    assert parsed.get("provenance", {}).get("schema") == 1, parsed
    return parsed, out


def test_bench_full_size_preset_refuses_without_a_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu", LFKT_BENCH_PRESET="llama3-8b")
    for script in ("bench.py", "bench_server.py"):
        out = subprocess.run(
            [sys.executable, os.path.join(REPO, script)],
            env=env, capture_output=True, text=True, timeout=300)
        assert out.returncode != 0, script
        assert '"metric"' not in out.stdout, out.stdout[-500:]
        assert "found 'cpu'" in out.stderr, out.stderr[-500:]


def test_bench_tiny_smoke():
    parsed, out = _run("bench.py")
    assert out.returncode == 0, out.stderr[-2000:]
    assert parsed["value"] > 0
    assert "chunk_sweep" in parsed
    # label honesty: the tiny config can't take the fused q4k layout
    assert "int8" in parsed["metric"]


def test_bench_ttft_sweep_tiny_smoke():
    """--ttft-sweep: one valid JSON line PER grid point (ctx × chunk),
    each carrying the pipeline attribution (chunk, overlap, kv_unroll)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", LFKT_BENCH_PRESET="tiny",
               LFKT_BENCH_TTFT_SWEEP="1")
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"), "--ttft-sweep"],
        env=env, capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-2000:]
    lines = [json.loads(ln) for ln in out.stdout.splitlines() if ln.strip()]
    points = [p for p in lines if "ttft-sweep" in p.get("metric", "")]
    # tiny grid: 2 contexts × (mono + chunk16) = 4 points
    assert len(points) == 4, out.stdout
    assert {p["n_ctx"] for p in points} == {64, 128}
    assert {p["prefill_chunk"] for p in points} == {0, 16}
    for p in points:
        assert p["value"] > 0
        assert p["unit"] == "ms"
        assert "kv_unroll" in p and "prefill_overlap" in p
        assert len(p["samples_ms"]) == 5


def test_bench_multiturn_replay_tiny_smoke():
    """--multiturn-replay (LFKT_BENCH_REPLAY=1): the paged radix-cache
    replay must emit one valid JSON line whose hit ratio is REAL (> 0) —
    the acceptance gate that warm turns actually resume from cached
    pages, with warm-turn prefill reduced by the matched prefix."""
    parsed, out = _run("bench.py", extra_env={"LFKT_BENCH_REPLAY": "1",
                                              "LFKT_BENCH_CONVS": "2",
                                              "LFKT_BENCH_TURNS": "3"})
    assert out.returncode == 0, out.stderr[-2000:]
    assert parsed["value"] > 0                     # warm-turn TTFT p50
    assert parsed["prefix_hit_ratio"] > 0, parsed
    assert parsed["reused_tokens_total"] > 0
    assert parsed["warm_turns"] >= 2
    assert parsed["pool"]["pages_used"] > 0
    # every turn past the very first must have found SOME cached prefix
    warm = [t for t in parsed["per_turn"] if t["conv"] + t["turn"] > 0]
    assert all(t["reused_tokens"] > 0 for t in warm), parsed["per_turn"]


def test_bench_server_tiny_smoke():
    parsed, out = _run("bench_server.py",
                       extra_env={"LFKT_BENCH_N_REQ": "4",
                                  "LFKT_BENCH_MAX_TOKENS": "16",
                                  "LFKT_BENCH_PORT": "8041"})
    assert out.returncode == 0, out.stderr[-2000:]
    assert parsed["value"] > 0
    assert parsed["concurrent"]["completed"] > 0
    # counter-based aggregate throughput (not len(oks)*max_tokens)
    assert parsed["concurrent"]["gen_tokens_total"] > 0
    assert parsed["concurrent"]["agg_tok_s"] > 0


def test_bench_server_disagg_smoke():
    """The disagg arm (LFKT_BENCH_DISAGG=1): the two-role loopback run
    must emit one valid JSON line where the split phase REALLY crossed
    the page wire (remote prefills > 0, pages on the wire) next to a
    role-off control phase of the same fresh-prompt workload — TTFT +
    aggregate tok/s for both arms (serving/disagg/)."""
    parsed, out = _run("bench_server.py",
                       extra_env={"LFKT_BENCH_DISAGG": "1",
                                  "LFKT_BENCH_N_REQ": "3",
                                  "LFKT_BENCH_MAX_TOKENS": "12",
                                  "LFKT_BENCH_PORT": "8045"})
    assert out.returncode == 0, out.stderr[-2000:]
    assert "disagg-loopback" in parsed["metric"]
    assert parsed["value"] > 0                     # split-arm TTFT p50
    for arm in ("control", "disagg"):
        assert parsed[arm]["samples"] == 3, parsed[arm]
        assert parsed[arm]["ttft_ms_p50"] > 0
        assert parsed[arm]["gen_tokens"] > 0
        assert parsed[arm]["agg_tok_s"] > 0
    cli = parsed["disagg_client"]
    assert cli["remote_prefills"] == 3, cli        # every split-arm prompt
    assert cli["local_fallbacks"] == 0, cli        # ... hopped, cleanly
    svc = parsed["disagg_service"]
    assert svc["prefills_served"] == 3 and svc["pages_sent"] > 0, svc
    assert svc["bytes_sent"] > 0


def test_bench_server_fleet_smoke():
    """The fleet arm (LFKT_BENCH_FLEET=1): two in-process paged replicas
    behind the real prefix-affinity router, the affinity replay vs the
    round-robin control — one valid provenance-stamped JSON line where
    the affinity phase genuinely reused cache (hit ratio > 0) and beat
    (or at worst matched) the control (serving/fleet/)."""
    parsed, out = _run("bench_server.py",
                       extra_env={"LFKT_BENCH_FLEET": "1",
                                  "LFKT_BENCH_CONVS": "3",
                                  "LFKT_BENCH_TURNS": "3",
                                  "LFKT_BENCH_MAX_TOKENS": "8",
                                  "LFKT_BENCH_PORT": "8047"})
    assert out.returncode == 0, out.stderr[-2000:]
    assert "fleet_prefix_hit_ratio" in parsed["metric"]
    aff, ctl = parsed["affinity"], parsed["control"]
    assert aff["policy"] == "affinity"
    assert ctl["policy"] == "roundrobin"
    # the affinity phase reused cached prefixes and never erred
    assert parsed["value"] > 0
    assert aff["hit_ratio_tokens"] == parsed["value"]
    assert aff["errors"] == [] and ctl["errors"] == [], (aff, ctl)
    assert aff["warm_samples"] > 0 and ctl["warm_samples"] > 0
    assert aff["warm_ttft_ms_p50"] > 0
    # both replicas actually took traffic in both phases
    for phase in (aff, ctl):
        assert len(phase["per_replica"]) == 2
        assert all(r["prompt_tokens"] > 0 for r in phase["per_replica"])
    # the A/B direction: affinity >= control (the decisive >= 2x margin
    # is pinned by the two-process drill in tests/test_fleet.py; tiny
    # prompts + page flooring make this smoke directional only)
    assert aff["hit_ratio_tokens"] >= ctl["hit_ratio_tokens"], parsed


def test_bench_server_batch_multiturn_smoke():
    """The lane-prefix A/B mode (LFKT_BENCH_MULTITURN x LFKT_BENCH_BATCH)
    must emit valid JSON with complete conversations and the engine-level
    scheduler stats.  (Reuse itself can't show at tiny scale: n_ctx 256
    can't hold a persona + 400-char-clip history, so history either
    overflows or is truncated away — the mechanism is pinned at engine
    level in tests/test_continuous.py.)"""
    parsed, out = _run("bench_server.py",
                       extra_env={"LFKT_BENCH_MULTITURN": "1",
                                  "LFKT_BENCH_BATCH": "2",
                                  "LFKT_LANE_PREFIX_CACHE": "1",
                                  "LFKT_PREFILL_CHUNK": "16",
                                  "LFKT_BENCH_TURNS": "3",
                                  "LFKT_BENCH_MAX_TOKENS": "12",
                                  "LFKT_MAX_CONTEXT_TOKENS": "100",
                                  "LFKT_BENCH_PORT": "8042"})
    assert out.returncode == 0, out.stderr[-2000:]
    assert parsed["value"] > 0
    assert parsed["turns_completed"] == [3, 3], parsed
    assert parsed["stream_errors"] == [], parsed
    assert "lane_prefix_hits" in parsed["scheduler_stats"], parsed


def test_bench_server_mixed_models_smoke():
    """The mixed-model arm (LFKT_BENCH_MIXED_MODELS x LFKT_BENCH_BATCH):
    two continuous engines behind a ModelRegistry, model= alternating
    across lanes via /v1/chat/completions, per-model aggregate tok/s in
    the provenance-stamped result (docs/MULTIMODEL.md)."""
    parsed, out = _run("bench_server.py",
                       extra_env={"LFKT_BENCH_MIXED_MODELS": "1",
                                  "LFKT_BENCH_BATCH": "2",
                                  "LFKT_BENCH_N_REQ": "4",
                                  "LFKT_BENCH_MAX_TOKENS": "12",
                                  "LFKT_BENCH_PORT": "8043"})
    assert out.returncode == 0, out.stderr[-2000:]
    assert parsed["value"] > 0
    assert set(parsed["per_model"]) == {"alpha", "beta"}
    for name in ("alpha", "beta"):
        pm = parsed["per_model"][name]
        assert pm["completed"] > 0 and pm["errors"] == 0, parsed
        assert pm["agg_tok_s"] > 0 and pm["gen_tokens"] > 0, parsed
    # the merged scheduler stats carry per-model keys + the HPA gauges
    stats = parsed["scheduler_stats"]
    assert stats["models"] == 2
    assert "alpha_lanes_live" in stats and "beta_lanes_live" in stats
    assert "adm_budget_tokens" in stats and "lane_idle_seconds" in stats


def test_synth_q4km_layouts_match_prep():
    """The q4km synthetic grid must stay layout-identical (pytree keys,
    shapes, dtypes) to what models/params.py builds from a real Q4_K_M
    file via prep_q4k/prep_q6k — otherwise the headline bench measures a
    layout no real file serves, and drift only surfaces on-chip."""
    import dataclasses

    import numpy as np

    sys.path.insert(0, REPO)
    from bench import synth_params_device
    from llama_fastapi_k8s_gpu_tpu.gguf.quants import quant_q4_k, quant_q6_k
    from llama_fastapi_k8s_gpu_tpu.models.config import LLAMA3_8B
    from llama_fastapi_k8s_gpu_tpu.ops.pallas.q6matmul import prep_q6k
    from llama_fastapi_k8s_gpu_tpu.ops.pallas.qmatmul import prep_q4k

    # smallest config whose every linear passes q4k_compatible on TPU
    # tiling (K % 2048 == 0, N % 128 == 0)
    cfg = dataclasses.replace(
        LLAMA3_8B, vocab_size=256, dim=2048, n_layers=2, n_heads=16,
        n_kv_heads=1, ffn_dim=4096, n_ctx=64)
    params = synth_params_device(cfg, fmt="q4km")

    rng = np.random.default_rng(0)

    def ref(prep, quant, n_out, k_in):
        w = rng.standard_normal(n_out * k_in).astype(np.float32)
        return prep(quant(w), n_out, k_in)

    kv_dim = cfg.n_kv_heads * 128
    expect_q4k = {"wq": (cfg.dim, cfg.dim), "wk": (kv_dim, cfg.dim),
                  "wo": (cfg.dim, cfg.dim), "w_gate": (cfg.ffn_dim, cfg.dim),
                  "w_up": (cfg.ffn_dim, cfg.dim)}
    expect_q6k = {"wv": (kv_dim, cfg.dim), "w_down": (cfg.dim, cfg.ffn_dim)}
    for name, (n, k) in expect_q4k.items():
        want = ref(prep_q4k, quant_q4_k, n, k)
        got = params["layers"][name]
        assert sorted(got) == sorted(want), name
        for key in want:
            assert got[key].shape == (cfg.n_layers, *want[key].shape), (name, key)
            assert got[key].dtype == want[key].dtype, (name, key)
    for name, (n, k) in expect_q6k.items():
        want = ref(prep_q6k, quant_q6_k, n, k)
        got = params["layers"][name]
        assert sorted(got) == sorted(want), name
        for key in want:
            assert got[key].shape == (cfg.n_layers, *want[key].shape), (name, key)
            assert got[key].dtype == want[key].dtype, (name, key)
    # output head: unstacked Q6_K
    want = ref(prep_q6k, quant_q6_k, cfg.vocab_size, cfg.dim)
    got = params["output"]
    assert sorted(got) == sorted(want)
    for key in want:
        assert got[key].shape == want[key].shape, key
        assert got[key].dtype == want[key].dtype, key


# ---------------------------------------------------------------------------
# lfkt-perf (ISSUE 7): provenance stamps + the perf_gate regression sentinel
# ---------------------------------------------------------------------------

def _load_tool(name):
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, "tools", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_provenance_stamp_schema(monkeypatch):
    """utils/provenance.stamp(): the block every bench line now carries —
    git commit of this checkout, a device kind, and the LFKT_* env
    fingerprint whose hash changes iff a knob changes."""
    from llama_fastapi_k8s_gpu_tpu.utils import provenance

    monkeypatch.setenv("LFKT_BENCH_PRESET", "tiny")
    s1 = provenance.stamp()
    assert s1["schema"] == 1
    assert len(s1["git_commit"]) == 40          # a real checkout commit
    assert s1["device"].startswith(("cpu", "tpu", "gpu"))
    assert s1["knobs"]["LFKT_BENCH_PRESET"] == "tiny"
    assert len(s1["knob_hash"]) == 12
    monkeypatch.setenv("LFKT_BENCH_PRESET", "other")
    assert provenance.stamp()["knob_hash"] != s1["knob_hash"]
    # run-placement knobs (port, dirs) are NOT part of the fingerprint —
    # a rerun from another checkout/port must not read as config drift
    monkeypatch.setenv("LFKT_BENCH_PRESET", "tiny")
    monkeypatch.setenv("LFKT_PORT", "8099")
    monkeypatch.setenv("LFKT_MODEL_DIR", "/tmp/elsewhere")
    s3 = provenance.stamp()
    assert s3["knob_hash"] == s1["knob_hash"]
    assert "LFKT_PORT" not in s3["knobs"]
    # schema validation accepts the real stamp...
    cm = _load_tool("check_manifest")
    assert cm.validate_schema(
        "x.json", {"metric": "m[t]", "value": 1.0, "unit": "ms",
                   "provenance": s1}) == []
    # ...and names each broken field
    broken = dict(s1, knobs={"NOT_LFKT": "x"}, git_commit="")
    errs = cm.validate_schema(
        "x.json", {"metric": "m[t]", "value": 1.0, "unit": "ms",
                   "provenance": broken})
    assert any("git_commit" in e for e in errs)
    assert any("knobs" in e for e in errs)
    # the memory axis (ISSUE 10): every stamp carries mem.rss_peak_bytes
    # (device_peak_bytes only where the backend reports memory_stats),
    # the peaks only grow, and check_manifest validates the block
    assert s1["mem"]["rss_peak_bytes"] > 0
    assert provenance.stamp()["mem"]["rss_peak_bytes"] >= \
        s1["mem"]["rss_peak_bytes"]
    errs = cm.validate_schema(
        "x.json", {"metric": "m[t]", "value": 1.0, "unit": "ms",
                   "provenance": dict(s1, mem={"rss_peak_bytes": -3,
                                               "bogus_field": 1})})
    assert any("rss_peak_bytes" in e for e in errs)
    assert any("bogus_field" in str(e) for e in errs)


def test_bench_emit_result_stamps_provenance(tmp_path):
    """bench.py's emit_result: every emitted line carries the stamp (unit
    level — the full-engine smoke paths above already cost minutes)."""
    import contextlib
    import io

    sys.path.insert(0, REPO)
    from bench import emit_result

    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        emit_result({"metric": "m[unit-test]", "value": 1.0, "unit": "ms"})
    line = json.loads(buf.getvalue())
    assert line["metric"] == "m[unit-test]"
    assert line["provenance"]["schema"] == 1
    assert line["provenance"]["git_commit"]


def test_perf_gate_passes_banked_baselines():
    """Acceptance: zero exit comparing the banked baselines to themselves
    (the MANIFEST 'Perf gate baselines' table resolves and matches)."""
    gate = _load_tool("perf_gate")
    fresh = [os.path.join(REPO, "docs", "bench", a)
             for a in gate.load_baseline_table().values()]
    assert fresh, "MANIFEST must name perf-gate baselines"
    assert gate.main(fresh) == 0


def test_perf_gate_refuses_planted_regression(tmp_path):
    """Acceptance: a planted regression (headline rate down 20%, TTFT up
    40%) exits nonzero; a within-noise wiggle (−2%) passes."""
    gate = _load_tool("perf_gate")
    base_name = gate.load_baseline_table()["decode_tokens_per_sec_per_chip"]
    base = json.load(open(os.path.join(REPO, "docs", "bench", base_name)))

    regressed = dict(base, value=base["value"] * 0.8,
                     ttft_ms_p50=base["ttft_ms_p50"] * 1.4)
    p = tmp_path / "regressed.json"
    p.write_text(json.dumps(regressed))
    assert gate.main([str(p)]) == 1

    wiggle = dict(base, value=base["value"] * 0.98)
    p2 = tmp_path / "wiggle.json"
    p2.write_text(json.dumps(wiggle))
    assert gate.main([str(p2)]) == 0


def test_perf_gate_comparability_guards(tmp_path):
    """Device mismatch refuses the comparison (exit 2); knob-fingerprint
    drift warns by default and refuses under --strict-knobs; an artifact
    carrying an error field is always refused."""
    gate = _load_tool("perf_gate")
    base_name = gate.load_baseline_table()["decode_tokens_per_sec_per_chip"]
    base_path = os.path.join(REPO, "docs", "bench", base_name)
    base = json.load(open(base_path))

    wrong_dev = dict(base, device="cpu:TFRT")
    p = tmp_path / "dev.json"
    p.write_text(json.dumps(wrong_dev))
    assert gate.main([str(p)]) == 2

    prov_a = dict(base, provenance={"schema": 1, "git_commit": "a" * 40,
                                    "device": "tpu:x", "knobs": {},
                                    "knob_hash": "aaaaaaaaaaaa"})
    prov_b = dict(base, provenance={**prov_a["provenance"],
                                    "knob_hash": "bbbbbbbbbbbb"})
    pa = tmp_path / "a.json"
    pb = tmp_path / "b.json"
    pa.write_text(json.dumps(prov_a))
    pb.write_text(json.dumps(prov_b))
    assert gate.main([str(pa), "--baseline", str(pb)]) == 0        # warns
    assert gate.main([str(pa), "--baseline", str(pb),
                      "--strict-knobs"]) == 2

    failed = dict(base, error="device fell over")
    pf = tmp_path / "f.json"
    pf.write_text(json.dumps(failed))
    assert gate.main([str(pf)]) == 1


def test_perf_gate_skips_unknown_tags_loudly(tmp_path):
    """A fresh config with no exact-metric baseline is SKIPPED (exit 0,
    reported) — never silently compared across configurations."""
    gate = _load_tool("perf_gate")
    rec = {"metric": "decode_tokens_per_sec_per_chip[tiny,novel-cfg]",
           "value": 1.0, "unit": "tokens/sec/chip", "device": "cpu:x"}
    p = tmp_path / "novel.json"
    p.write_text(json.dumps(rec))
    assert gate.main([str(p)]) == 0
