#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the serving path runs on the chip.

    python chip_smoke.py              # one TPU chip (what the driver runs)

Drives Llama-3-8B Q4_K_M at full width and depth (random weights from a
seed, written here as a GGUF file by the package's own writer) through the
entry point users call — ``python -m llama_fastapi_k8s_gpu_tpu.server`` —
and checks what comes out.  One JSON object per phase goes to stdout; the
last line is ``{"ok": true, "device": {...}}`` and nothing else, or
``{"ok": false, "phase": ..., "error": ...}`` with a non-zero exit.

One process for each chip: this parent never imports JAX (nor anything of
the package that does).  Every phase is one child that holds the chip and
has exited — waited for — before the next starts.  The two file writers
are numpy only and run beside the kernel phase.

Numbers printed here (seconds, TTFT, tokens/s) are a smoke's, from a
handful of requests, not a benchmark's.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))
WORK = os.path.join(HERE, ".chip_smoke")          # git-ignored scratch
PKG = "llama_fastapi_k8s_gpu_tpu"
DEADLINE_S = 1150                                 # the driver allows 1200
MODEL = "llama3-8b-q4km.gguf"                     # full width, 32 layers
MODEL_2L = "llama3-8b-q4km-2layer.gguf"           # full width, 2 layers

#: tolerances, stated once.  Every error is ||got - want|| / ||want|| over
#: the whole output.  The fused kernels keep their per-sub-block scales and
#: mins and their dequantized planes in bf16 (2^-9 relative each: about
#: 0.5 % in all, the same in interpret mode), flash attention rounds its
#: probabilities to bf16 (about 0.3 %).
KERNEL_TOL = 1e-2
ATTN_TOL = 1e-2
LOGIT_TOL = 5e-2     # q4k+pallas against bf16+xla, full width, 2 layers

BODY = {   # the reference's request shape (tests/test_server.py)
    "bot_profile": {"name": "Alice.f",
                    "appearance": "tall,slim,blonde,loves cats,hates rain"},
    "user_profile": {"name": "Bob"},
    "context": [
        {"turn": "user", "message": "hi"},
        {"turn": "assistant", "message": "hey"},
        {"turn": "user", "message": "how are you?"},
    ],
}


class PhaseFailed(Exception):
    def __init__(self, phase: str, error: str):
        super().__init__(f"{phase}: {error}")
        self.phase, self.error = phase, error


def emit(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


# ---------------------------------------------------------------------------
# parent: process plumbing (no JAX)
# ---------------------------------------------------------------------------

_children: list[subprocess.Popen] = []


def _spawn(args: list[str], env: dict, log_name: str) -> subprocess.Popen:
    """Start a child in its own process group, stdout piped, stderr to a
    log under WORK."""
    err = open(os.path.join(WORK, log_name), "wb")
    proc = subprocess.Popen(
        [sys.executable, *args], cwd=HERE, env=env, stdout=subprocess.PIPE,
        stderr=err, start_new_session=True)
    proc._err_file = err          # closed in _reap
    proc._log = err.name
    _children.append(proc)
    return proc


def _reap(proc: subprocess.Popen, grace: float = 10.0) -> int:
    """Make sure ``proc`` (and its group) is gone; returns its exit code."""
    if proc.poll() is None:
        for sig in (signal.SIGTERM, signal.SIGKILL):
            try:
                os.killpg(proc.pid, sig)
            except ProcessLookupError:
                break
            try:
                proc.wait(timeout=grace)
                break
            except subprocess.TimeoutExpired:
                continue
    rc = proc.wait()
    if proc.stdout:
        proc.stdout.close()
    proc._err_file.close()
    if proc in _children:
        _children.remove(proc)
    return rc


def _log_tail(path: str, n: int = 1500) -> str:
    try:
        with open(path, "rb") as f:
            f.seek(0, os.SEEK_END)
            f.seek(max(0, f.tell() - n))
            return f.read().decode("utf-8", "replace")
    except OSError:
        return ""


def child_env(extra: dict | None = None) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = HERE + os.pathsep + env.get("PYTHONPATH", "")
    env.setdefault("TPU_LOG_DIR", "disabled")
    env.update(extra or {})
    return env


def run_child(phase: str, argv: list[str], timeout: float) -> dict:
    """Run ``chip_smoke.py --child <phase> ...`` to its end; forward its
    JSON lines; return the last one.  Any other outcome is a failure."""
    proc = _spawn([os.path.abspath(__file__), "--child", phase, *argv],
                  child_env(), f"{phase}.err")
    timer = threading.Timer(timeout, lambda: os.killpg(proc.pid, signal.SIGKILL))
    timer.start()
    last = None
    try:
        for raw in proc.stdout:
            line = raw.decode("utf-8", "replace").strip()
            if line.startswith("{"):
                try:
                    last = json.loads(line)
                except ValueError:
                    continue
                if not last.get("final"):
                    emit(last)
    finally:
        timer.cancel()
        rc = _reap(proc)
    if rc != 0 or last is None or not last.get("final"):
        why = (last or {}).get("error") or _log_tail(proc._log)
        raise PhaseFailed(phase, f"child exited {rc}: {why}"[-2000:])
    last.pop("final")
    emit(last)
    if not last.get("ok"):
        raise PhaseFailed(phase, str(last.get("error")))
    return last


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def http(method: str, url: str, body: dict | None = None,
         timeout: float = 60.0):
    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(
        url, data=data, method=method,
        headers={"content-type": "application/json"} if data else {})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, r.read().decode("utf-8", "replace")
    except urllib.error.HTTPError as e:
        return e.code, e.read().decode("utf-8", "replace")


def post_response(base: str) -> dict:
    t0 = time.time()
    status, text = http("POST", base + "/response", BODY, timeout=120)
    dt = time.time() - t0
    reply = ""
    if status == 200:
        reply = json.loads(text).get("response", "")
    return {"status": status, "seconds": round(dt, 3),
            "reply_chars": len(reply), "reply_head": reply[:160],
            "error": None if status == 200 else text[:300]}


def post_stream(base: str) -> dict:
    """POST /response/stream; TTFT = first content chunk, rate = content
    chunks after the first over the time they took."""
    req = urllib.request.Request(
        base + "/response/stream", data=json.dumps(BODY).encode(),
        method="POST", headers={"content-type": "application/json"})
    t0 = time.time()
    ttft = None
    chunks = 0
    done = False
    text = []
    with urllib.request.urlopen(req, timeout=120) as r:
        status = r.status
        for raw in r:
            line = raw.decode("utf-8", "replace").strip()
            if not line.startswith("data:"):
                continue
            payload = line[5:].strip()
            if payload == "[DONE]":
                done = True
                break
            try:
                doc = json.loads(payload)
            except ValueError:
                continue
            if "error" in doc:
                return {"status": status, "done": False, "error": payload[:300]}
            piece = (doc.get("choices") or [{}])[0].get("delta", {}).get("content")
            if piece:
                if ttft is None:
                    ttft = time.time() - t0
                chunks += 1
                text.append(piece)
    total = time.time() - t0
    rate = None
    if ttft is not None and chunks > 1 and total > ttft:
        rate = round((chunks - 1) / (total - ttft), 2)
    return {"status": status, "done": done,
            "ttft_ms_smoke": None if ttft is None else round(ttft * 1e3, 1),
            "chunks": chunks, "chunks_per_s_smoke": rate,
            "reply_chars": len("".join(text)), "seconds": round(total, 3)}


def metric_value(metrics_text: str, name: str) -> float | None:
    for line in metrics_text.splitlines():
        if line.startswith(name + " ") or line.startswith(name + "{"):
            try:
                return float(line.rsplit(" ", 1)[1])
            except ValueError:
                return None
    return None


def server_phase(phase: str, model_name: str, extra_env: dict,
                 n_sequential: int, n_concurrent: int,
                 deadline: float) -> dict:
    """Start the server through its normal entry point, wait until it is
    ready, send the requests, read back what it says about itself, SIGTERM
    it and wait for it to exit."""
    port = free_port()
    base = f"http://127.0.0.1:{port}"
    env = child_env({
        "LFKT_MODEL_DIR": WORK, "LFKT_MODEL_NAME": model_name,
        "LFKT_HOST": "127.0.0.1", "LFKT_PORT": str(port), **extra_env})
    t0 = time.time()
    proc = _spawn(["-m", f"{PKG}.server"], env, f"{phase}.err")
    out: dict = {"phase": phase, "ok": False, "settings": extra_env}
    try:
        while True:
            if proc.poll() is not None:
                raise PhaseFailed(phase, f"server exited {proc.returncode} "
                                  f"before it was ready: {_log_tail(proc._log)}")
            if time.time() > deadline:
                raise PhaseFailed(phase, "server not ready before the "
                                  f"deadline: {_log_tail(proc._log)}")
            try:
                status, text = http("GET", base + "/health", timeout=5)
            except OSError:
                status = None
            if status == 200:
                break
            time.sleep(1.0)
        out["ready_s"] = round(time.time() - t0, 1)
        health = json.loads(text)
        eng = health.get("engine") or {}
        out["health_state"] = health.get("state")
        out["attn_impl"] = eng.get("attn_impl")
        out["weight_formats"] = eng.get("weight_formats")
        out["kv_dtype"] = eng.get("kv_dtype")
        out["load_phases"] = eng.get("load_phases")
        out["native_lib"] = eng.get("native_lib")

        out["requests"] = [post_response(base) for _ in range(n_sequential)]
        out["stream"] = post_stream(base)
        if n_concurrent:
            results: list = [None] * n_concurrent

            def one(i):
                try:
                    results[i] = post_response(base)
                except Exception as e:  # recorded, and failed on below
                    results[i] = {"status": None, "error": repr(e)}

            threads = [threading.Thread(target=one, args=(i,))
                       for i in range(n_concurrent)]
            tc = time.time()
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            out["concurrent"] = results
            out["concurrent_wall_s"] = round(time.time() - tc, 3)

        status, metrics = http("GET", base + "/metrics")
        if status != 200:
            raise PhaseFailed(phase, f"/metrics answered {status}")
        out["metrics_lines"] = len(metrics.splitlines())
        out["decode_tokens_per_sec_p50_smoke"] = metric_value(
            metrics, "engine_decode_tokens_per_sec_p50")
        out["ttft_seconds_p50_smoke"] = metric_value(
            metrics, "engine_ttft_seconds_p50")
        status, text = http("GET", base + "/debug/compiles")
        if status != 200:
            raise PhaseFailed(phase, f"/debug/compiles answered {status}")
        comp = json.loads(text)
        out["degrades"] = comp.get("degrades")
        out["compiles"] = sum(p.get("compiles", 0)
                              for p in comp.get("programs", []))
        out["compile_seconds"] = round(sum(
            p.get("compile_seconds_total", 0.0)
            for p in comp.get("programs", [])), 1)
        out["persistent_cache"] = comp.get("persistent_cache")
        status, text = http("GET", base + "/debug/memory")
        if status == 200:
            mem = json.loads(text)
            out["memory"] = {k: mem.get(k) for k in
                             ("attributed_bytes", "ground_truth",
                              "residual_bytes", "headroom")}

        # drain: SIGTERM, then the process must exit by itself
        t1 = time.time()
        os.killpg(proc.pid, signal.SIGTERM)
        try:
            rc = proc.wait(timeout=60)
        except subprocess.TimeoutExpired:
            raise PhaseFailed(phase, "server still running 60 s after SIGTERM")
        out["drain_s"] = round(time.time() - t1, 1)
        out["exit_code"] = rc
    finally:
        _reap(proc)

    # -- the checks: nothing here may hide the device ----------------------
    problems = []
    if out["health_state"] != "READY":
        problems.append(f"health state {out['health_state']!r}")
    if out["attn_impl"] != "pallas":
        problems.append(f"attn_impl {out['attn_impl']!r}, want 'pallas'")
    fm = out["weight_formats"] or {}
    want = {"wq": "q4k-fused", "wk": "q4k-fused", "wv": "q6k-fused",
            "wo": "q4k-fused", "w_gate": "q4k-fused", "w_up": "q4k-fused",
            "w_down": "q6k-fused"}
    if fm != want:
        problems.append(f"weight_formats {fm}, want {want}")
    if not out["native_lib"]:
        problems.append("native C++ load path not in use (numpy codecs)")
    if out["degrades"]:
        problems.append(f"degrade ledger not empty: {out['degrades']}")
    for r in out["requests"] + (out.get("concurrent") or []):
        if r.get("status") != 200 or not r.get("reply_chars"):
            problems.append(f"request: {r}")
    st = out["stream"]
    if st.get("status") != 200 or not st.get("done") or not st.get("reply_chars"):
        problems.append(f"stream: {st}")
    if out.get("exit_code") != 0:
        problems.append(f"server exit code {out.get('exit_code')} after SIGTERM")
    out["ok"] = not problems
    if problems:
        out["error"] = "; ".join(problems)[:2000]
    emit(out)
    if problems:
        raise PhaseFailed(phase, out["error"])
    return out


def start_writer(name: str, n_layers: int, seed: int) -> subprocess.Popen:
    return _spawn(
        [os.path.abspath(__file__), "--child", "write", "--path",
         os.path.join(WORK, name), "--layers", str(n_layers),
         "--seed", str(seed)],
        child_env({"JAX_PLATFORMS": "cpu"}), f"write_{n_layers}.err")


def wait_writer(proc: subprocess.Popen, timeout: float) -> dict:
    try:
        out, _ = proc.communicate(timeout=timeout)
    except subprocess.TimeoutExpired:
        _reap(proc)
        raise PhaseFailed("write", "file writer timed out")
    rc = _reap(proc)
    lines = [l for l in out.decode().splitlines() if l.startswith("{")]
    if rc != 0 or not lines:
        raise PhaseFailed("write", f"writer exited {rc}: {_log_tail(proc._log)}")
    doc = json.loads(lines[-1])
    doc.pop("final", None)
    emit(doc)
    return doc


def device_phase() -> dict:
    """The device facts, from a child that held the chip.  The child itself
    fails a run without a TPU or in interpret mode."""
    dev = run_child("device", [], timeout=180)
    if dev.get("count") != 1:
        raise PhaseFailed("device", f"{dev.get('count')} device(s), want 1")
    return dev


def run_one_chip(args, deadline: float) -> dict:
    dev = device_phase()
    # the writers need no chip: they run beside the kernel phase
    w2 = start_writer(MODEL_2L, 2, args.seed)
    w32 = start_writer(MODEL, 32, args.seed)
    run_child("kernels", ["--seed", str(args.seed)], timeout=600)
    wait_writer(w2, 400)
    run_child("logits2", ["--path", os.path.join(WORK, MODEL_2L),
                          "--seed", str(args.seed)], timeout=400)
    os.remove(os.path.join(WORK, MODEL_2L))
    wait_writer(w32, 600)
    first = server_phase("server", MODEL, {},
                         n_sequential=3, n_concurrent=0, deadline=deadline)
    lanes = server_phase("server_lanes8", MODEL,
                         {"LFKT_BATCH_SIZE": "8"}, n_sequential=1,
                         n_concurrent=8, deadline=deadline)
    # Reported, not required: the lane engine vmaps its steps, so none
    # of its decode programs is one the serial engine compiled.  What the cache saves shows from one RUN
    # to the next, in both phases' hits and warm-up seconds.
    emit({"phase": "compile_cache", "ok": True,
          "dir": (lanes.get("persistent_cache") or {}).get("dir"),
          "first_phase": first.get("persistent_cache"),
          "second_phase": lanes.get("persistent_cache"),
          "first_phase_warmup_s": (first.get("load_phases") or {}).get("warmup_s"),
          "second_phase_warmup_s": (lanes.get("load_phases") or {}).get("warmup_s")})
    return dev


def parent_main(args) -> int:
    deadline = time.time() + DEADLINE_S
    if not os.path.isdir(os.path.join(HERE, PKG)):
        emit({"ok": False, "phase": "start",
              "error": f"{PKG}/ is not next to chip_smoke.py"})
        return 1
    shutil.rmtree(WORK, ignore_errors=True)
    os.makedirs(WORK, exist_ok=True)
    try:
        dev = run_one_chip(args, deadline)
    except PhaseFailed as e:
        emit({"ok": False, "phase": e.phase, "error": e.error})
        return 1
    except Exception as e:   # a fault of the smoke itself is a failure too
        emit({"ok": False, "phase": "parent", "error": repr(e)})
        return 1
    finally:
        for proc in list(_children):
            _reap(proc)
        for name in os.listdir(WORK):              # keep the logs only
            if name.endswith(".gguf"):
                os.remove(os.path.join(WORK, name))
    emit({"ok": True, "device": {"platform": dev["platform"],
                                 "kind": dev["kind"], "count": dev["count"]}})
    return 0


# ---------------------------------------------------------------------------
# children: each is one process that holds the chip (or, for `write`, numpy)
# ---------------------------------------------------------------------------

def final(obj: dict) -> None:
    emit({**obj, "final": True})


def child_write(args) -> None:
    from llama_fastapi_k8s_gpu_tpu.testing import write_llama3_8b_q4km_gguf

    t0 = time.time()
    write_llama3_8b_q4km_gguf(args.path, n_layers=args.layers, seed=args.seed)
    final({"phase": "write", "ok": True, "layers": args.layers,
           "file": os.path.basename(args.path),
           "file_gb": round(os.path.getsize(args.path) / 1e9, 2),
           "write_s": round(time.time() - t0, 1)})


def measure_dispatch_rtt_s(n: int = 7) -> float:
    """Median wall time of a minimal jitted dispatch + host fetch: what every
    host round trip to the chip costs before any work.  Two warm executions
    (the compile, and the first run) are discarded first."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: x + 1)
    x = jnp.zeros((), jnp.int32)
    for _ in range(2):
        int(f(x))
    ts = []
    for _ in range(n):
        t0 = time.perf_counter()
        int(f(x))
        ts.append(time.perf_counter() - t0)
    return sorted(ts)[n // 2]


def child_device(args) -> None:
    import importlib.metadata as md

    import jax

    from llama_fastapi_k8s_gpu_tpu.ops.pallas import use_interpret
    from llama_fastapi_k8s_gpu_tpu.utils.jaxcache import setup_compile_cache

    cache_dir = setup_compile_cache()
    devs = jax.devices()
    versions = {}
    for pkg in ("jax", "jaxlib", "libtpu", "numpy"):
        try:
            versions[pkg] = md.version(pkg)
        except md.PackageNotFoundError:
            versions[pkg] = None
    on_chip = devs[0].platform == "tpu" and not use_interpret()
    out = {"phase": "device", "ok": on_chip,
           "error": None if on_chip else "JAX found no TPU: this smoke never "
                                         "reports a CPU run as a pass",
           "platform": devs[0].platform,
           "kind": devs[0].device_kind, "count": len(devs),
           "interpret": use_interpret(), "versions": versions,
           "python": sys.version.split()[0], "compile_cache_dir": cache_dir}
    if devs[0].platform == "tpu":
        out["dispatch_rtt_ms"] = round(measure_dispatch_rtt_s(n=21) * 1e3, 4)
    final(out)


def _require_tpu(count: int):
    """The devices, or a failed phase when they are not ``count`` TPU chips."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) != count:
        raise SystemExit(f"this phase needs {count} TPU chip(s); JAX found "
                         f"{len(devs)} x {devs[0].platform}")
    return devs


def _rel_err(got, want) -> float:
    import numpy as np

    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    if got.shape != want.shape or not np.isfinite(got).all():
        return float("inf")
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def child_kernels(args) -> None:
    """Every main-path kernel, COMPILED (interpret=False passed explicitly)
    at the 8B shapes, against a plain oracle: a float32 matmul over the
    numpy codecs' dequantized weights (gguf/quants.py), and the XLA
    score-matrix attention."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from llama_fastapi_k8s_gpu_tpu import native
    from llama_fastapi_k8s_gpu_tpu.gguf import quants
    from llama_fastapi_k8s_gpu_tpu.gguf.constants import GGMLType
    from llama_fastapi_k8s_gpu_tpu.models.config import LLAMA3_8B
    from llama_fastapi_k8s_gpu_tpu.models.llama import xla_attention
    from llama_fastapi_k8s_gpu_tpu.ops.pallas import (
        flash_attention, prep_q4k, prep_q6k, q4k_matmul, q4k_matmul_stacked,
        q6k_matmul, q6k_matmul_stacked)
    from llama_fastapi_k8s_gpu_tpu.testing import rand_q4k_blocks, rand_q6k_blocks
    from llama_fastapi_k8s_gpu_tpu.utils.jaxcache import setup_compile_cache

    setup_compile_cache()
    _require_tpu(1)
    rng = np.random.default_rng(args.seed)
    fmts = {
        "q4k": (GGMLType.Q4_K, rand_q4k_blocks, prep_q4k, q4k_matmul,
                q4k_matmul_stacked),
        "q6k": (GGMLType.Q6_K, rand_q6k_blocks, prep_q6k, q6k_matmul,
                q6k_matmul_stacked),
    }
    layer_shapes = [(4096, 4096), (4096, 1024), (4096, 14336), (14336, 4096)]
    cases = [(f, k, n, True) for f in ("q4k", "q6k") for k, n in layer_shapes]
    cases.append(("q6k", 4096, 128256, False))           # the output head
    rows, worst = [], 0.0
    t0 = time.time()
    for fmt, K, N, stacked in cases:
        gtype, blocks, prep, mm, mm_stacked = fmts[fmt]
        raw = blocks(rng, N * K)
        W = quants.dequantize(raw, gtype, N * K).reshape(N, K)
        w = prep(raw, N, K)
        for B in ((1, 8) if not stacked else (1, 256)):
            x = jnp.asarray(rng.standard_normal((B, K), dtype=np.float32),
                            jnp.bfloat16).astype(jnp.float32)
            want = np.asarray(x) @ W.T
            errs = {"unstacked": _rel_err(mm(x, w, interpret=False), want)}
            if stacked:
                ws = {k: jnp.stack([jnp.zeros_like(v), v]) for k, v in w.items()}
                errs["stacked"] = _rel_err(
                    mm_stacked(x, ws, 1, interpret=False), want)
                del ws
            for kind, e in errs.items():
                rows.append({"kernel": f"{fmt}_matmul", "kind": kind,
                             "k": K, "n": N, "rows": B, "err": round(e, 5)})
                worst = max(worst, e)
        del W, w, raw
    if native.loaded_path() is None:
        raise SystemExit("the weight planes were packed by numpy, not by the "
                         "native library")
    emit({"phase": "kernels", "part": "fused_matmul", "tolerance": KERNEL_TOL,
          "worst_err": round(worst, 5), "cases": rows,
          "native_lib": native.loaded_path(),
          "seconds": round(time.time() - t0, 1)})
    ok = worst <= KERNEL_TOL

    # flash attention at the 8B head layout, each prefill bucket up to
    # n_ctx 1024, and one chunk that starts inside the ring
    cfg = dataclasses.replace(LLAMA3_8B, n_ctx=1024)
    k = jnp.asarray(rng.standard_normal((8, 1024, 128), dtype=np.float32),
                    jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((8, 1024, 128), dtype=np.float32),
                    jnp.bfloat16)
    arows, aworst = [], 0.0
    t0 = time.time()
    for S, pos in ((128, 0), (256, 0), (512, 0), (1024, 0), (128, 300)):
        q = jnp.asarray(rng.standard_normal((S, 32, 128), dtype=np.float32),
                        jnp.bfloat16)
        got = flash_attention(q, k, v, jnp.int32(pos), sm_scale=128 ** -0.5,
                              interpret=False).reshape(S, 4096)
        want = xla_attention(q, k, v, None, None, pos + jnp.arange(S), cfg,
                             jnp.float32)
        e = _rel_err(got, want)
        arows.append({"kernel": "flash_attention", "s": S, "pos": pos,
                      "err": round(e, 5)})
        aworst = max(aworst, e)
    emit({"phase": "kernels", "part": "flash_attention", "tolerance": ATTN_TOL,
          "worst_err": round(aworst, 5), "cases": arows,
          "seconds": round(time.time() - t0, 1)})
    ok = ok and aworst <= ATTN_TOL
    final({"phase": "kernels", "ok": ok,
           "worst_matmul_err": round(worst, 5), "matmul_tolerance": KERNEL_TOL,
           "worst_attention_err": round(aworst, 5), "attention_tolerance": ATTN_TOL,
           "error": None if ok else "a kernel is outside its tolerance"})


def _prefill_logits(path: str, env: dict, ids: list[int]):
    """Load ``path`` the way the server does (settings → engine kwargs) and
    return the prefill logits at the last prompt token as float32."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from llama_fastapi_k8s_gpu_tpu.engine import Engine
    from llama_fastapi_k8s_gpu_tpu.models.generate import prefill_jit
    from llama_fastapi_k8s_gpu_tpu.models.llama import init_cache
    from llama_fastapi_k8s_gpu_tpu.server.app import _base_engine_kwargs
    from llama_fastapi_k8s_gpu_tpu.utils.config import get_settings

    os.environ.update(env)
    eng = Engine(path, **_base_engine_kwargs(get_settings()))
    bucket = next(b for b in eng.prefill_buckets if b >= len(ids))
    padded = jnp.asarray(ids + [0] * (bucket - len(ids)), jnp.int32)
    logits, _ = prefill_jit(eng.params, eng.cfg, padded, jnp.int32(len(ids)),
                            init_cache(eng.cfg))
    out = np.asarray(jax.device_get(logits), np.float32)
    fmts = sorted({next(iter(sorted(leaf))) for leaf in eng.params["layers"].values()
                   if isinstance(leaf, dict)})
    info = {"attn_impl": eng.cfg.attn_impl, "plane_keys": fmts,
            "bucket": bucket, "load_phases": eng.load_phases}
    del eng
    return out, info


def child_logits2(args) -> None:
    """Full width, two layers: the served configuration (q4k + pallas)
    against the same file served as bf16 + xla — both existing settings."""
    import jax
    import numpy as np

    from llama_fastapi_k8s_gpu_tpu.utils.jaxcache import setup_compile_cache

    setup_compile_cache()
    _require_tpu(1)
    rng = np.random.default_rng(args.seed)
    ids = [int(t) for t in rng.integers(0, 128000, 200)]
    # `auto` would serve a model this shallow as bf16: name the 8B's format
    got, ginfo = _prefill_logits(
        args.path, {"LFKT_WEIGHT_FORMAT": "q4k", "LFKT_ATTN_IMPL": "pallas"}, ids)
    want, winfo = _prefill_logits(
        args.path, {"LFKT_WEIGHT_FORMAT": "bf16", "LFKT_ATTN_IMPL": "xla"}, ids)
    finite = bool(np.isfinite(got).all() and np.isfinite(want).all())
    rel = float(np.linalg.norm(got - want) / np.linalg.norm(want)) if finite \
        else float("inf")
    ok = (finite and rel <= LOGIT_TOL and got.shape == (128256,)
          and ginfo["attn_impl"] == "pallas" and winfo["attn_impl"] == "xla"
          and winfo["plane_keys"] == ["w"] and "w" not in ginfo["plane_keys"])
    final({"phase": "logits2", "ok": ok, "layers": 2, "prompt_tokens": len(ids),
           "rel_l2_err": round(rel, 5), "tolerance": LOGIT_TOL,
           "argmax_equal": bool(got.argmax() == want.argmax()),
           "logit_rms": round(float(np.sqrt(np.mean(want ** 2))), 4),
           "served": ginfo, "reference": winfo,
           "error": None if ok else "served logits disagree with bf16+xla"})


CHILDREN = {"write": child_write, "device": child_device,
            "kernels": child_kernels, "logits2": child_logits2}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--child", choices=sorted(CHILDREN), help=argparse.SUPPRESS)
    ap.add_argument("--path", help=argparse.SUPPRESS)
    ap.add_argument("--layers", type=int, default=32, help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.child:
        try:
            CHILDREN[args.child](args)
        except SystemExit as e:
            if isinstance(e.code, str):
                final({"phase": args.child, "ok": False, "error": e.code})
                return 1
            raise
        return 0
    return parent_main(args)


if __name__ == "__main__":
    sys.exit(main())
