"""Deterministic CPU perf pins (ISSUE 7): compile & dispatch budgets.

Chip time is scarce; compile counts and dispatch counts are not — they
are exact, device-independent integers the devtime registry
(obs/devtime.py) measures identically on the CPU backend.  These tests
pin, for both engines:

- **warmup compiles exactly K programs** (named, counted): a new jit
  entry point, a lost warmup shape, or a silent extra signature changes
  K and fails here — on CPU, long before a chip session pays for it;
- **steady state compiles nothing**: after warmup, requests re-dispatch
  the warmed programs only (this pin found and now guards two real
  holes: a second decode chunk's donated-state compile, fixed by the
  two-chunk warmup, and the serial tail-chunk compile,
  fixed by always dispatching full chunks — pinned below);
- **each request dispatches exactly D per program** — an extra dispatch
  per decode chunk is launch/DMA overhead; it must never sneak in
  unmeasured.

The pins run in ONE fresh subprocess: jit caches are process-global, so
a suite that already warmed the module-level entry points would satisfy
any compile count vacuously.  Shapes: tiny GGUF, n_ctx=128, buckets
(32, 64, 128), decode_chunk=4.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_SCRIPT = r"""
import os
os.environ["JAX_PLATFORMS"] = "cpu"
flags = [f for f in os.environ.get("XLA_FLAGS", "").split()
         if "xla_force_host_platform_device_count" not in f]
flags.append("--xla_force_host_platform_device_count=8")
os.environ["XLA_FLAGS"] = " ".join(flags)
import json, sys, tempfile, time
import jax
jax.config.update("jax_platforms", "cpu")
from llama_fastapi_k8s_gpu_tpu.testing import write_tiny_llama_gguf
from llama_fastapi_k8s_gpu_tpu.obs.devtime import DEVTIME
from llama_fastapi_k8s_gpu_tpu.engine import ContinuousEngine, Engine

path = tempfile.mktemp(suffix=".gguf")
write_tiny_llama_gguf(path)
MSGS = [{"role": "user", "content": "Say something."}]
KW = dict(n_ctx=128, decode_chunk=4, max_gen_tokens=16,
          prefill_buckets=(32, 64, 128))
out = {}


def snap():
    return {k: (v["compiles"], v["dispatches"])
            for k, v in DEVTIME.counters().items()
            if v["compiles"] or v["dispatches"]}


def delta(a, b):
    return {k: (b[k][0] - a.get(k, (0, 0))[0], b[k][1] - a.get(k, (0, 0))[1])
            for k in b if b[k] != a.get(k, (0, 0))}


# -- serial ---------------------------------------------------------------
DEVTIME.reset()
eng = Engine(path, prefix_cache=False, **KW)
eng.warmup()
w = snap()
out["serial_warmup"] = w
eng.create_chat_completion(MSGS, temperature=0.0, max_tokens=9)
a = snap()
out["serial_req"] = delta(w, a)
eng.create_chat_completion(MSGS, temperature=0.0, max_tokens=9)
b = snap()
out["serial_req2"] = delta(a, b)
# a budget that leaves a tail: 1 + 4 + 4 + 2 of a third, full chunk
r = eng.create_chat_completion(MSGS, temperature=0.0, max_tokens=11)
out["serial_tail"] = delta(b, snap())
out["serial_tail_tokens"] = {"completion": (r["usage"]["completion_tokens"], 0)}

# -- continuous ------------------------------------------------------------
DEVTIME.reset()
ceng = ContinuousEngine(path, batch_size=4, **KW)
ceng.warmup()
w = snap()
out["cont_warmup"] = w
ceng.submit(MSGS, temperature=0.0, max_tokens=8).result(timeout=120)
time.sleep(0.5)         # let the pipelined in-flight chunk land
out["cont_req"] = delta(w, snap())
ceng.shutdown()

print("PINS " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def pins():
    proc = subprocess.run([sys.executable, "-c", _SCRIPT], cwd=REPO,
                          capture_output=True, text=True, timeout=420)
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    line = next(ln for ln in proc.stdout.splitlines()
                if ln.startswith("PINS "))
    return {k: {p: tuple(v) for p, v in progs.items()}
            for k, progs in json.loads(line[5:]).items()}


def _compiles(d):
    return {k: v[0] for k, v in d.items() if v[0]}


# ---------------------------------------------------------------------------
# warmup compiles exactly K programs, by name and count
# ---------------------------------------------------------------------------

def test_serial_warmup_compile_budget(pins):
    # prefill: the warmup prompt's bucket (64) + the remaining bucket walk
    # (128; bucket 32 never runs monolithically for this prompt) = 2
    # programs; decode_chunk: ONE n_steps=4 signature covers both warmup
    # chunks; first_sample: 1
    assert _compiles(pins["serial_warmup"]) == {
        "prefill": 2, "first_sample": 1, "decode_chunk": 1}


def test_continuous_warmup_compile_budget(pins):
    # prefill_chunk: 3 admission/suffix slice shapes; first_sample,
    # lane_write, lane_decode_chunk and lane_cache_copy (the lane-prefix
    # snapshot program): ONE each.  Every leaf of the process is placed
    # plainly on the one device, so no program is built twice under two
    # names of one placement
    assert _compiles(pins["cont_warmup"]) == {
        "prefill_chunk": 3, "first_sample": 1, "lane_decode_chunk": 1,
        "lane_write": 1, "lane_cache_copy": 1}


# ---------------------------------------------------------------------------
# steady state: zero compiles, exactly D dispatches per request
# ---------------------------------------------------------------------------

def test_serial_request_dispatch_budget(pins):
    # max_tokens=9 = first sample + two FULL decode chunks of 4: one
    # prefill dispatch, one first-sample, exactly two chunk dispatches —
    # and zero compiles, twice in a row
    want = {"prefill": (0, 1), "first_sample": (0, 1),
            "decode_chunk": (0, 2)}
    assert pins["serial_req"] == want
    assert pins["serial_req2"] == want


def test_serial_budget_tail_compiles_nothing(pins):
    """A budget that is not 1 + k * decode_chunk ends in a FULL chunk whose
    surplus is dropped on the host: ``n_steps`` is static, and a shorter
    tail was a program warm-up never compiled (found on the chip: ~19 s of
    compile inside the first 512-token request, PR 22)."""
    assert pins["serial_tail"] == {
        "prefill": (0, 1), "first_sample": (0, 1), "decode_chunk": (0, 3)}
    assert pins["serial_tail_tokens"]["completion"][0] == 11


# ---------------------------------------------------------------------------
# per-decode-step KERNEL-LAUNCH pin: counted deterministically on CPU, via
# the jaxpr launch audit (obs/launches.py) — launch primitives weighted by
# layer-loop trip count
# ---------------------------------------------------------------------------

def _launch_audit():
    from llama_fastapi_k8s_gpu_tpu.models.config import ModelConfig
    from llama_fastapi_k8s_gpu_tpu.models.params import synth_params
    from llama_fastapi_k8s_gpu_tpu.obs.launches import decode_step_launches

    cfg = ModelConfig(vocab_size=64, dim=64, n_layers=8, n_heads=4,
                      n_kv_heads=2, ffn_dim=96, n_ctx=32)
    return decode_step_launches(synth_params(cfg), cfg)


def test_per_layer_decode_step_launch_pin():
    # the per-layer chain: 7 linears + 2 attention contractions = 9 launch
    # primitives per layer, × L=8 in the layer loop, + the output head.
    # A new dot on the decode path (or a lost loop) changes these exact
    # integers and fails here, on CPU, before any chip session pays for it.
    audit = _launch_audit()
    assert audit["loop_trips"] == [8]
    assert audit["in_loop"] == 8 * 9
    assert audit["outside"] == 1          # the output head
    # the ONE loop whose trip count is not static: the decode attention's
    # read of the ring in blocks up to the newest live slot (its 2
    # contractions are in the 9 above, counted once: a floor)
    assert audit["while_loops"] == 1


def test_continuous_request_budget(pins):
    d = pins["cont_req"]
    # zero compiles anywhere: admission, lane write, decode, harvest
    assert all(c == 0 for c, _ in d.values()), d
    assert d.get("prefill_chunk") == (0, 1)
    assert d.get("lane_write") == (0, 1)
    assert d.get("first_sample") == (0, 1)
    # 8 tokens = 2 chunks; the pipelined scheduler may have one extra
    # in-flight wave dispatched at harvest time (bounded, never compiled)
    chunks = d.get("lane_decode_chunk", (0, 0))[1]
    assert 2 <= chunks <= 4, d
