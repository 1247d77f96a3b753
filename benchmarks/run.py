#!/usr/bin/env python3
"""One run of one cell of the benchmark, in a new process.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Finds the cell in ``BENCHMARK.json`` (or, for the CPU rehearsal, in
``benchmarks/rehearsal/cells.json`` or a file of its own,
``benchmarks/rehearsal/cells/<name>.json``), writes or finds the
configuration's GGUF file, starts ``python -m llama_fastapi_k8s_gpu_tpu.server`` on it as a
child (this parent never touches JAX's devices: the child holds the chip),
waits for READY, warms up, offers the cell's traffic to
``/v1/chat/completions`` with ``stream: true`` over loopback for
``--seconds``, reads the program's debug surfaces, stops the server and
prints the result as the last line of standard output.

Nothing here names a model, a block, a cell or a length: a configuration,
its block of layers, a traffic mix, a kernel name group and a per-layer
metric are each a file of their own, found by the name ``BENCHMARK.json``
or the configuration file gives.

Exit codes: 0 a result was printed; 2 bad arguments or files; 3 no result
(no accelerator, too few chips, the server failed).
"""

from __future__ import annotations

import time

T_START = time.time()          # set-up is counted from here

import argparse                # noqa: E402
import asyncio                 # noqa: E402
import hashlib                 # noqa: E402
import importlib.util          # noqa: E402
import json                    # noqa: E402
import os                      # noqa: E402
import shutil                  # noqa: E402
import sys                     # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)

import client                  # noqa: E402
import ggufgen                 # noqa: E402
import metrics                 # noqa: E402
import server as srv           # noqa: E402
import traffic                 # noqa: E402
import xplane                  # noqa: E402

CACHE = os.path.join(HERE, ".cache")
READY_DEADLINE_S = 1100        # a first run compiles; the driver allows 1200
PROBES = ("tell me about the weather on the coast today",
          "what would you cook for six people on a sunday")
PROBE_TOKENS = 16
OP_NAME_CHARS = 240            # the profiler names an operation by its HLO text


class NoResult(Exception):
    """The run ends with no result line (exit code 3)."""


def log(msg: str) -> None:
    print(f"[bench +{time.time() - T_START:6.1f}s] {msg}", file=sys.stderr,
          flush=True)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


# ---------------------------------------------------------------------------
# the cell: found by name, never by code
# ---------------------------------------------------------------------------

def find_cell(name: str) -> dict:
    """{"cell", "config", "mix", "end_to_end", "per_layer", "rehearsal"}."""
    bench_path = os.path.join(ROOT, "BENCHMARK.json")
    bench = load_json(bench_path)
    cell = next((w for w in bench["workloads"] if w["name"] == name), None)
    rehearsal = False
    if cell is not None:
        entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
        cfg_path = os.path.join(ROOT, entry["file"])
        mix_path = os.path.join(HERE, "traffic", cell["traffic"] + ".json")
    else:
        cells = load_json(os.path.join(HERE, "rehearsal", "cells.json"))
        cell = next((w for w in cells["workloads"] if w["name"] == name), None)
        own = os.path.join(HERE, "rehearsal", "cells", name + ".json")
        if cell is None and os.path.exists(own):
            cell = load_json(own)
        if cell is None or cell.get("name") != name:
            raise SystemExit(f"no cell {name!r} in BENCHMARK.json, in "
                             "benchmarks/rehearsal/cells.json or as "
                             "benchmarks/rehearsal/cells/<name>.json")
        rehearsal = True
        cfg_path = os.path.join(HERE, "rehearsal", cell["config"] + ".json")
        mix_path = os.path.join(HERE, "rehearsal", cell["traffic"] + ".json")

    def mine(metric: dict) -> bool:
        return rehearsal or "workloads" not in metric \
            or name in metric["workloads"]

    return {
        "cell": cell, "config": load_json(cfg_path), "mix": load_json(mix_path),
        "end_to_end": [m for m in bench["end_to_end"] if mine(m)],
        "per_layer": [m for m in bench["per_layer"] if mine(m)],
        "rehearsal": rehearsal,
    }


def kernel_groups() -> dict[str, list[str]]:
    out = {}
    kdir = os.path.join(HERE, "kernels")
    for fn in sorted(os.listdir(kdir)):
        if fn.endswith(".json"):
            doc = load_json(os.path.join(kdir, fn))
            out[doc["name"]] = list(doc["patterns"])
    return out


def layer_metric_reader(name: str):
    """The ``read(run)`` of ``layer_metrics/<name>.py``, or None."""
    path = os.path.join(HERE, "layer_metrics", name + ".py")
    if not os.path.exists(path):
        return None
    spec = importlib.util.spec_from_file_location(
        "layer_metric_" + name.replace("-", "_").replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# ---------------------------------------------------------------------------
# set-up
# ---------------------------------------------------------------------------

def ensure_gguf(cfg: dict) -> str:
    """The configuration's GGUF file in the git-ignored cache, written on
    a cell's first run in a checkout and found again by later runs."""
    os.makedirs(CACHE, exist_ok=True)
    shape_keys = sorted(k for k, v in cfg.items()
                        if isinstance(v, (int, float)) or v is None)
    # a configuration of the dense block names none, and keeps its file
    named = [["block", cfg["block"]]] if "block" in cfg else []
    key = json.dumps([[k, cfg[k]] for k in shape_keys] + named
                     + [cfg["gguf"]], sort_keys=True)
    digest = hashlib.sha256(key.encode()).hexdigest()[:12]
    path = os.path.join(CACHE, f"{cfg['name']}-{digest}.gguf")
    if not os.path.exists(path):
        t0 = time.time()
        size = ggufgen.write_gguf(cfg, path)
        log(f"wrote {path} ({size / 1e9:.2f} GB) in {time.time() - t0:.1f}s")
    return path


def server_env(cfg: dict, trace: int, work: str) -> dict:
    """The deployment's ``LFKT_*`` settings from the configuration file, and
    the program's tracing on (every request sampled, captures allowed) only
    in a traced run."""
    env = dict(cfg["serve"]["env"])
    env["LFKT_TRACE_SAMPLE"] = "1" if trace else "0"
    env["LFKT_TRACE_RING"] = "16384"
    if trace:
        env["LFKT_PROFILE_DIR"] = os.path.join(work, "profile")
    if cfg.get("platform") == "cpu":   # the rehearsal keeps its compiles apart
        env["JAX_PLATFORMS"] = "cpu"
        env["JAX_COMPILATION_CACHE_DIR"] = os.path.join(CACHE, "xla-cpu")
    return env


async def one_request(host, port, body) -> client.Record:
    rec = client.Record(index=-1, due=time.time(),
                        max_tokens=body.get("max_tokens", 0))
    return await client.stream_chat(host, port, body, rec)


async def probe(host, port) -> list[str]:
    """Two temperature-0 prompts, one after the other, on an idle server."""
    out = []
    for text in PROBES:
        rec = await one_request(host, port, {
            "messages": [{"role": "user", "content": text}], "stream": True,
            "stream_options": {"include_usage": True},
            "max_tokens": PROBE_TOKENS, "temperature": 0.0})
        if metrics.failure(rec) is not None:
            raise NoResult(f"probe request failed: {metrics.failure(rec)}")
        out.append("".join(rec.text))
    return out


async def warm_up(host, port, mix) -> int:
    """Measure the chat template's overhead, then run the shapes the mix
    uses: its longest and shortest prompts, as many at once as it has
    callers.  Returns the overhead in tokens."""
    n_probe = 8
    req = traffic.Request(-1, None, 0, 2, 1)
    body = traffic.body(mix, req, 0)
    body["messages"][1]["content"] = " ".join(
        ggufgen.word(i * 31) for i in range(n_probe))
    rec = await one_request(host, port, body)
    if metrics.failure(rec) is not None or rec.prompt_tokens is None:
        raise NoResult(f"warm-up request failed: {metrics.failure(rec)}")
    overhead = rec.prompt_tokens - n_probe - int(mix["system_tokens"])
    lengths = sorted({p for p, _ in mix["lengths"]}, reverse=True)
    wide = int(mix.get("clients", 4))
    picks = [lengths[0], lengths[-1]] * ((wide + 1) // 2)
    async def later(i, p):
        await asyncio.sleep(i * client.RAMP_S)
        return await one_request(host, port, traffic.body(
            mix, traffic.Request(-1, None, p, 20, 1000 + i), overhead))

    recs = await asyncio.gather(*[later(i, p)
                                  for i, p in enumerate(picks[:wide])])
    for r in recs:
        if metrics.failure(r) is not None:
            raise NoResult(f"warm-up request failed: {metrics.failure(r)}")
    off = max(abs(r.prompt_tokens - p) / p for r, p in zip(recs, picks))
    if off > 0.05:
        raise NoResult(f"prompt lengths are {off:.1%} off their targets")
    return overhead


async def wait_idle(server, timeout: float = 5.0) -> None:
    """Until no lane is live and nothing is queued (as far as the program
    says), so that the probes run alone."""
    t_end = time.time() + timeout
    while time.time() < t_end:
        status, text = await asyncio.to_thread(server.get, "/metrics")
        live = srv.parse_gauge(text, "scheduler_lanes_live") or 0
        pending = srv.parse_gauge(text, "scheduler_pending") or 0
        depth = srv.parse_gauge(text, "queue_depth") or 0
        if status == 200 and live + pending + depth == 0:
            return
        await asyncio.sleep(0.1)


# ---------------------------------------------------------------------------
# the measured window and what is read beside it
# ---------------------------------------------------------------------------

def sampler(server, samples: list, hz: float = 5.0):
    async def job(t0, t1):
        while time.time() < t1:
            t = time.time()
            status, text = await asyncio.to_thread(server.get, "/metrics")
            if status == 200:
                samples.append((t, text))
            await asyncio.sleep(max(0.0, 1.0 / hz - (time.time() - t)))
    return job


def profiler(server, out: dict, seconds: float, wait: float = 120.0):
    """Ask the program for one capture of ``seconds`` at 40 % of the window.
    The capture lands in the directory this run set (``LFKT_PROFILE_DIR``)
    and is read from there; the answer is only noted, so one that comes
    late or not at all costs no metric."""
    async def job(t0, t1):
        await asyncio.sleep(max(0.0, t0 + 0.4 * (t1 - t0) - time.time()))
        out["asked_s"] = seconds
        out["t_send"] = time.time()
        try:
            status, text = await asyncio.to_thread(
                server.get, f"/debug/profile?seconds={seconds}", wait)
            out["doc"] = json.loads(text) if status == 200 \
                else {"error": text[:200]}
            out["status"] = status
        except (OSError, ValueError) as e:
            out["error"] = f"{type(e).__name__}: {e}"[:200]
        out["t_recv"] = time.time()
    return job


async def measure(server, cellinfo, args) -> dict:
    host, port = "127.0.0.1", server.port
    mix = cellinfo["mix"]
    overhead = await warm_up(host, port, mix)
    probes_before = await probe(host, port)
    compiles_before = server.get_json("/debug/compiles")
    depth_before = server.get_json("/health").get("queue_depth")
    samples, prof = [], {}
    side = []
    if args.trace:
        side = [sampler(server, samples),
                profiler(server, prof, min(3.0, args.seconds / 3.0))]
    setup_s = time.time() - T_START
    log(f"set-up done ({setup_s:.1f}s); measuring for {args.seconds}s")
    window = await client.drive(host, port, mix, args.seed, args.seconds,
                                overhead, side)
    for res in window["side"]:
        if isinstance(res, BaseException):
            log(f"side job failed: {res!r}")
    depth_after = server.get_json("/health").get("queue_depth")
    memory = server.get_json("/debug/memory")
    await wait_idle(server)
    probes_after = await probe(host, port)
    compiles_after = server.get_json("/debug/compiles")
    traces = []
    if args.trace:
        for r in window["records"]:
            if r.request_id:
                status, text = await asyncio.to_thread(
                    server.get, "/debug/traces/" + r.request_id)
                if status == 200:
                    traces.append(json.loads(text))
    return {
        **window, "setup_s": setup_s, "overhead_tokens": overhead,
        "probes_same": probes_before == probes_after,
        "compiles_after": compiles_after,
        "compiles_in_window": srv.total_compiles(compiles_after)
        - srv.total_compiles(compiles_before),
        "queue_depth": (depth_before, depth_after), "memory": memory,
        "samples": samples, "profile_call": prof, "traces": traces,
    }


# ---------------------------------------------------------------------------
# the result
# ---------------------------------------------------------------------------

def reduced_capture(run: dict) -> dict | None:
    """The mid-window capture, reduced; its window is the capture's own
    ``time.sleep`` of the seconds this run asked for."""
    path = xplane.capture_of(run)
    if not path:
        return None
    trace = xplane.load(path)
    asked = run["profile_call"].get("asked_s")
    return xplane.reduce(
        trace, run["kernel_groups"],
        xplane.capture_window(trace["host"], asked) if asked else None)


def why_left_out(run: dict, device: dict) -> str:
    """Why a traced run prints fewer metrics than its cell declares.  A
    sound one on the chip prints them all."""
    if device.get("platform") != "tpu":
        return f"platform {device.get('platform')!r}: no device to trace"
    if not run.get("capture_path"):
        return "no capture file under " + str(run.get("profile_dir"))
    if run.get("profile") is None:
        return "no operation ran on the device inside the capture"
    return "the reader found nothing to read in this run"


def breakdown(profile: dict | None) -> dict | None:
    """The ten device operations with most self time, and the idle gaps by
    what the host's Python frames say it was doing (``xplane.label_gaps``)."""
    if not profile:
        return None
    ops = sorted(profile["ops"].items(), key=lambda kv: -kv[1])[:10]
    program_files = {fn for _, _, files in os.walk(os.path.join(ROOT, srv.PROGRAM))
                     for fn in files if fn.endswith(".py")}
    return {"device_ops": [[k[:OP_NAME_CHARS], v] for k, v in ops],
            "idle_gaps": xplane.label_gaps(profile["gaps"], profile["host"],
                                           program_files)}


def is_correct(run: dict, cellinfo, health: dict, device: dict,
               n_failed: int, n_attempted: int) -> tuple[bool, list[str]]:
    why = []
    if cellinfo["rehearsal"]:
        why.append("a rehearsal is never correct")
    if device.get("platform") != "tpu":
        why.append(f"platform {device.get('platform')!r}")
    eng = health.get("engine") or {}
    for key, want in (cellinfo["config"].get("expect_health") or {}).items():
        if eng.get(key) != want:
            why.append(f"/health engine.{key} is {eng.get(key)!r}, want {want!r}")
    if run["compiles_after"].get("degrades"):
        why.append(f"degrade ledger: {run['compiles_after']['degrades']}")
    if run["compiles_in_window"]:
        why.append(f"{run['compiles_in_window']} compiles inside the window")
    if not run["probes_same"]:
        why.append("temperature-0 probes changed over the window")
    if n_attempted == 0 or n_failed / n_attempted >= 0.01:
        why.append(f"{n_failed} of {n_attempted} requests failed")
    return not why, why


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cellinfo = find_cell(args.workload)
    cfg, cell = cellinfo["config"], cellinfo["cell"]
    want_platform = cfg.get("platform", "tpu")

    # refuse before a file is written: held to another platform, no program
    if want_platform != "cpu" and want_platform not in os.environ.get(
            "JAX_PLATFORMS", want_platform).split(","):
        log(f"no result: JAX_PLATFORMS={os.environ['JAX_PLATFORMS']}, this "
            f"cell runs on {want_platform!r} only")
        return 3
    sys.path.insert(1, ROOT)
    if importlib.util.find_spec(srv.PROGRAM) is None:
        log(f"no result: the program ({srv.PROGRAM}) is not in this checkout")
        return 3

    work = os.path.join(CACHE, "work", cell["name"])
    shutil.rmtree(work, ignore_errors=True)
    os.makedirs(work)
    env = server_env(cfg, args.trace, work)
    model = ensure_gguf(cfg)
    server = srv.Server(model, env, work)
    try:
        health = server.wait_ready(T_START + READY_DEADLINE_S, want_platform)
        dev = server.device()
        if dev["count"] < int(cell["chips"]):
            raise NoResult(f"{dev['count']} devices, the cell needs "
                           f"{cell['chips']}")
        ready_s = time.time() - T_START
        log(f"server READY after {ready_s:.1f}s on {dev['kind']}")
        run = asyncio.run(measure(server, cellinfo, args))
        health = server.get_json("/health")
    except (NoResult, srv.ServerFailed) as e:
        log(f"no result: {e}")
        server.stop(grace=20.0)
        return 3
    rc = server.stop()
    device = server.device() or dev
    log(f"server stopped (exit code {rc})")

    records = run["records"]
    attempted = len(records)
    failed = sum(metrics.failure(r) is not None for r in records)
    e2e = metrics.end_to_end(records, run["t0"], run["t1"])
    e2e["setup_s"] = run["setup_s"]

    run.update(config=cfg, mix=cellinfo["mix"], cell=cell, health=health,
               device=device, ready_s=ready_s, e2e=e2e,
               kernel_groups=kernel_groups(), notes={},
               profile_dir=env.get("LFKT_PROFILE_DIR"))
    profile = None
    if args.trace:
        profile = run["profile"] = reduced_capture(run)
        call = run["profile_call"]
        run["notes"]["capture"] = {
            "asked_s": call.get("asked_s"), "file": run["capture_path"],
            "answer": call.get("error") or call.get("doc") or "none"}
        values = {m["name"]: reader(run) for m in cellinfo["per_layer"]
                  if (reader := layer_metric_reader(m["name"]))}
    else:
        values = e2e
    declared = cellinfo["per_layer"] if args.trace else cellinfo["end_to_end"]
    out_metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in declared if values.get(m["name"]) is not None}
    if len(out_metrics) < len(declared):
        run["notes"]["left_out"] = {
            "metrics": [m["name"] for m in declared
                        if m["name"] not in out_metrics],
            "why": why_left_out(run, device) if args.trace
            else "the window holds no sample of it"}
        log(f"left out: {run['notes']['left_out']}")

    correct, why = is_correct(run, cellinfo, health, device, failed, attempted)
    dev_out = {"platform": device.get("platform"), "kind": device.get("kind"),
               "count": device.get("count"),
               "memory_peak_bytes": device.get("memory_peak_bytes") or None}
    if args.trace:
        on_chip = profile is not None and device.get("platform") == "tpu"
        dev_out["busy_s"] = profile["busy_s"] if on_chip else None
        dev_out["window_s"] = profile["window_s"] if on_chip else None
    if cellinfo["rehearsal"]:      # a CPU number under no metric's name
        dev_out["memory_peak_bytes"] = None
        for m in out_metrics.values():
            m["value"] = None

    print(json.dumps({
        "note": "diagnostics; the result is the last line",
        "workload": cell["name"], "seed": args.seed, "seconds": args.seconds,
        "why_not_correct": why, "ready_s": ready_s,
        "load_phases": (health.get("engine") or {}).get("load_phases"),
        "persistent_cache": run["compiles_after"].get("persistent_cache"),
        "finished": len(metrics.finished(records)),
        "cut": sum(r.cut for r in records),
        "failures": sorted({metrics.failure(r) for r in records
                            if metrics.failure(r)})[:5],
        "n_gaps": len(metrics.gaps_ms(records)),
        "n_tpot": len(metrics.tpots_ms(records)),
        "lateness": metrics.lateness_ms(run["lateness"]),
        "queue_depth_before_after": run["queue_depth"],
        ("cpu_rehearsal_values" if cellinfo["rehearsal"] else "end_to_end_all"): e2e,
        "server_exit": rc, "notes": run["notes"],
    }), flush=True)
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": out_metrics, "device": dev_out}
    if args.trace:
        bd = breakdown(profile)
        if bd:
            result["breakdown"] = bd
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
