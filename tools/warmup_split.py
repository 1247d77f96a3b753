"""Where a cached start's ``warmup_compile_s`` goes, program by program.

On the chip, for each configuration named: write the GGUF file as the
benchmark does, start the engine once to fill the persistent XLA cache (and
the executable store beside it), once more as a judged start would find it
(``programs_loaded`` / ``load_s`` in the line it prints), then a third and a
fourth time WITHOUT the store and with every entry program's FIRST call of a
signature taken apart with ``jax.stages`` (the third fills JAX's cache under
this tool's call sites, the fourth is the cached start's split)::

    fn.trace(...)      Python runs, a jaxpr
    .lower()           jaxpr -> StableHLO (every pallas_call -> Mosaic)
    .compile()         hash the module, read the executable (a cache hit)
    fn(...)            what the jit call still does after those three
    block_until_ready  the first execution

and, for what the executable store (PR 55; utils/execstore.py) pays
instead, the executable's serialised size and the seconds ``serialize`` /
``deserialize_and_load`` take.  Every start ends with one temperature-0
reply: all of them (built, loaded, through the jit) must be equal byte for
byte, else the exit code is 4; the host seconds of each entry program's
dispatches during it are in the start's line (``dispatch_host_us``: the
store's path beside the jit's).  One JSON document per start under
``chiprun_out/warmup_split/``, a table on stdout.

    chiprun -- python tools/warmup_split.py lfm2.chat-16sat mistral.chat-8sat

The parent never imports JAX (one process a chip): every start is a child.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPLY_PROMPT = ("Tell me, in a few sentences, how a sailing ship makes way "
                "against the wind, and what its crew has to do for it.")
REPLY_TOKENS = 400
OUT = os.path.join(ROOT, "chiprun_out", "warmup_split")


def child(out_path: str, staged: bool) -> None:
    """Build and warm the engine the server would, in this process."""
    sys.path.insert(0, ROOT)
    import jax

    from llama_fastapi_k8s_gpu_tpu.obs import devtime
    from llama_fastapi_k8s_gpu_tpu.server.app import _default_engine_factory
    from llama_fastapi_k8s_gpu_tpu.utils.config import get_settings
    from llama_fastapi_k8s_gpu_tpu.utils.jaxcache import (
        compile_cache_stats, setup_compile_cache)

    rows: list[dict] = []
    seen: set = set()
    plain = devtime._TimedJit.__call__

    def staged_call(self, *args, **kwargs):
        from jax.experimental import serialize_executable as se

        sig = (self._name, devtime._signature(args, kwargs))
        if sig in seen:
            return plain(self, *args, **kwargs)
        seen.add(sig)
        fn = self._fn
        t0 = time.perf_counter()
        traced = fn.trace(*args, **kwargs)
        t1 = time.perf_counter()
        lowered = traced.lower()
        t2 = time.perf_counter()
        compiled = lowered.compile()
        t3 = time.perf_counter()
        row = {"program": self._name, "trace_s": t1 - t0,
               "lower_s": t2 - t1, "compile_s": t3 - t2}
        try:
            payload, in_tree, out_tree = se.serialize(compiled)
            t4 = time.perf_counter()
            se.deserialize_and_load(payload, in_tree, out_tree)
            t5 = time.perf_counter()
            row.update(serialize_s=t4 - t3, load_s=t5 - t4,
                       payload_bytes=len(payload))
        except Exception as e:  # noqa: BLE001 -- a tool: say it and go on
            row["serialize_error"] = f"{type(e).__name__}: {e}"[:300]
        t6 = time.perf_counter()
        out = plain(self, *args, **kwargs)
        t7 = time.perf_counter()
        jax.block_until_ready(out)
        t8 = time.perf_counter()
        row.update(jit_call_s=t7 - t6, first_exec_s=t8 - t7)
        rows.append(row)
        return out

    t0 = time.time()
    setup_compile_cache()
    if staged:      # the jit path taken apart: without the executable store
        devtime._TimedJit.__call__ = staged_call
        if hasattr(devtime.DEVTIME, "use_store"):
            devtime.DEVTIME.use_store(None)
            # Engine.__init__ sets the compile cache up again: keep it off
            devtime.DEVTIME.use_store = lambda path: None
    eng = _default_engine_factory(get_settings())()
    engine_s = round(time.time() - t0, 3)
    warmup = next((p.doc(t0) for p in eng.startup.phases
                   if p.name == "warmup"), None)

    # after the warm-up: one temperature-0 reply (the parent holds the three
    # starts' replies against each other, byte for byte), with the host
    # seconds of every entry program's dispatch taken around the wrapper
    host: dict[str, list] = {}
    inner = plain        # every signature is seen by now: no stage left

    def clocked(self, *args, **kwargs):
        t = time.perf_counter()
        out = inner(self, *args, **kwargs)
        host.setdefault(self._name, []).append(time.perf_counter() - t)
        return out

    devtime._TimedJit.__call__ = clocked
    reply = eng.create_chat_completion(
        [{"role": "user", "content": REPLY_PROMPT}], max_tokens=REPLY_TOKENS,
        temperature=0.0)
    devtime._TimedJit.__call__ = inner
    store = getattr(devtime.DEVTIME, "store", None)
    doc = {"staged": staged, "engine_s": engine_s, "warmup": warmup,
           "persistent_cache": compile_cache_stats(), "rows": rows,
           "reply": reply["choices"][0]["message"]["content"],
           "reply_tokens": reply["usage"]["completion_tokens"],
           "dispatch_host_us": {
               name: {"n": len(v), "p50": round(1e6 * sorted(v)[len(v) // 2], 1),
                      "mean": round(1e6 * sum(v) / len(v), 1)}
               for name, v in host.items()},
           "executable_store": None if store is None else store.stats(),
           "compiles": {k: v["compiles"]
                        for k, v in devtime.DEVTIME.counters().items()
                        if v["compiles"]},
           # /debug/compiles' signatures, for telling two first calls of
           # one program apart (PERF.md section 7: ``prefill_chunk``)
           "signatures": {p["name"]: p["signature_list"]
                          for p in devtime.DEVTIME.snapshot()["programs"]
                          if p["signature_list"]}}
    with open(out_path, "w") as f:
        json.dump(doc, f, indent=1)
    os._exit(0)     # the scheduler thread of a lane engine is not ours to stop


def main(argv: list[str]) -> int:
    if argv and argv[0] == "--child":
        child(argv[1], argv[2] == "1")
        return 0
    sys.path.insert(0, os.path.join(ROOT, "benchmarks"))
    import run as bench      # numpy only: the parent stays off JAX

    os.makedirs(OUT, exist_ok=True)
    for cell_name in argv:
        info = bench.find_cell(cell_name)
        cfg = info["config"]
        model = bench.ensure_gguf(cfg)
        env = dict(os.environ)
        env.update(bench.server_env(cfg, 0, OUT))
        env.update({"LFKT_MODEL_DIR": os.path.dirname(model),
                    "LFKT_MODEL_NAME": os.path.basename(model),
                    "JAX_COMPILATION_CACHE_DIR":
                        os.path.join(ROOT, ".lfkt_xla_cache"),
                    "TPU_LOG_DIR": "disabled"})
        replies: list[str] = []
        # the staged start runs twice: JAX's cache key holds each Pallas
        # kernel's Mosaic module WITH its Python call sites, so the entries
        # the first two starts wrote (built from obs/devtime.py) are not the
        # ones a lowering from ``staged_call`` looks for: the first staged
        # start fills the cache under its own call sites, the second reads it
        for tag, staged in (("fill", "0"), ("cached", "0"),
                            ("staged-fill", "1"), ("staged", "1")):
            out = os.path.join(OUT, f"{cfg['name']}.{tag}.json")
            t0 = time.time()
            rc = subprocess.call([sys.executable, os.path.abspath(__file__),
                                  "--child", out, staged], env=env, cwd=ROOT)
            print(f"# {cfg['name']} {tag}: rc {rc} in {time.time() - t0:.1f}s",
                  flush=True)
            if rc != 0:
                return rc
            with open(out) as f:
                doc = json.load(f)
            w = doc["warmup"] or {}
            replies.append(doc["reply"])
            print(json.dumps({"config": cfg["name"], "start": tag,
                              "engine_s": doc["engine_s"],
                              "warmup_s": w.get("seconds"),
                              **{k: v for k, v in (w.get("attrs") or {}).items()
                                 if k != "top_programs"},
                              "dispatch_host_us": doc["dispatch_host_us"],
                              "executable_store": doc["executable_store"],
                              "reply_tokens": doc["reply_tokens"]}),
                  flush=True)
            if staged == "1":
                keys = ("trace_s", "lower_s", "compile_s", "jit_call_s",
                        "first_exec_s", "serialize_s", "load_s")
                for r in doc["rows"]:
                    print(json.dumps({"program": r["program"],
                                      **{k: round(r.get(k, 0.0), 3)
                                         for k in keys},
                                      "payload_MB": round(
                                          r.get("payload_bytes", 0) / 1e6, 2),
                                      **({"error": r["serialize_error"]}
                                         if "serialize_error" in r else {})}),
                          flush=True)
                tot = {k: round(sum(r.get(k, 0.0) for r in doc["rows"]), 3)
                       for k in keys}
                tot["payload_MB"] = round(sum(
                    r.get("payload_bytes", 0) for r in doc["rows"]) / 1e6, 2)
                print(json.dumps({"config": cfg["name"], "total": tot,
                                  "programs": len(doc["rows"])}), flush=True)
        # built, loaded, through the jit twice: one reply
        print(json.dumps({"config": cfg["name"],
                          "replies_equal": len(set(replies)) == 1,
                          "reply": replies[0][:120]}), flush=True)
        if len(set(replies)) != 1:
            for r in replies:
                print("# reply:", json.dumps(r), flush=True)
            return 4
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
