"""The tree has two engines, ``Engine`` and ``ContinuousEngine(Engine)``, one
device and one scheduler (PR 60).  What the deleted ``cycle`` batch entry,
the sequence-parallel engine and the mesh used to be held to, on the path
that stays:

- a batch through ``ContinuousEngine.create_chat_completions`` (the facade
  over ``submit``): order and shapes, a batch of one against the serial
  engine, padding invariance, overflow, an oversized prompt, a long
  neighbour; on the tiny dense, routed and latent files;
- greedy, stream and long-context parity of the lane engine with the serial
  one, at one lane and at four;
- every deployment file under ``benchmarks/`` (read only) sets knobs the
  registry still declares, and the server's factory picks one of the two
  classes from ``LFKT_BATCH_SIZE`` alone;
- the arrows of the package: ``models`` and ``ops`` import neither
  ``parallel`` nor ``engine``, ``parallel`` imports neither ``engine`` nor
  ``server``.

Tiny files, small contexts; nothing here compiles a whole stack.
"""

from __future__ import annotations

import ast
import glob
import json
import os
import time

import pytest

from llama_fastapi_k8s_gpu_tpu import testing
from llama_fastapi_k8s_gpu_tpu.engine import ContinuousEngine, Engine

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PACKAGE = os.path.join(REPO, "llama_fastapi_k8s_gpu_tpu")

# ---------------------------------------------------------------------------
# a batch through the lane engine
# ---------------------------------------------------------------------------

N_CTX = 128
LANES = 2
FILES = {
    "dense": (testing.write_tiny_llama_gguf, {}),
    "routed": (testing.write_tiny_olmoe_gguf, {"weight_format": "q4k"}),
    "latent": (testing.write_tiny_mla_gguf, {}),
}
KW = dict(n_ctx=N_CTX, decode_chunk=4, max_gen_tokens=16,
          prefill_buckets=(32, 64, 128), prefill_chunk=16)
SHORT = [{"role": "user", "content": "hi"}]
LONG = [{"role": "user", "content": "tell me a long story " * 4}]


def _text(out: dict) -> str:
    return out["choices"][0]["message"]["content"]


@pytest.fixture(scope="module")
def pairs(tmp_path_factory):
    """{file: (serial engine, lane engine)}, each built on first use and
    kept for the module (one build a worker and file)."""
    made: dict = {}

    def get(which: str):
        if which not in made:
            write, kw = FILES[which]
            path = str(tmp_path_factory.mktemp(which) / "tiny.gguf")
            write(path)
            made[which] = (
                Engine(path, prefix_cache=False, **KW, **kw),
                ContinuousEngine(path, batch_size=LANES,
                                 lane_prefix_cache=False, **KW, **kw))
        return made[which]

    yield get
    for _, lanes in made.values():
        lanes.shutdown()


def _order_and_shapes(serial, lanes):
    prompts = [[{"role": "user", "content": f"question number {i} " * (i + 1)}]
               for i in range(LANES)]
    outs = lanes.create_chat_completions(prompts, temperature=0.0,
                                         max_tokens=6)
    assert [o["object"] for o in outs] == ["chat.completion"] * LANES
    want = [serial.create_chat_completion(p, temperature=0.0, max_tokens=6)
            for p in prompts]
    # entry i answers prompt i: its prompt's length and the serial text
    assert [o["usage"]["prompt_tokens"] for o in outs] \
        == [w["usage"]["prompt_tokens"] for w in want]
    assert [_text(o) for o in outs] == [_text(w) for w in want]
    assert all(set(o["usage"]) == {"prompt_tokens", "completion_tokens",
                                   "total_tokens"} for o in outs)


def _a_batch_of_one_is_the_serial_engines_text(serial, lanes):
    a = serial.create_chat_completion(LONG, temperature=0.0, max_tokens=8)
    b, = lanes.create_chat_completions([LONG], temperature=0.0, max_tokens=8)
    assert _text(a) == _text(b)
    assert a["usage"] == b["usage"]
    assert a["choices"][0]["finish_reason"] == b["choices"][0]["finish_reason"]


def _padding_invariance(serial, lanes):
    alone, = lanes.create_chat_completions([SHORT], temperature=0.0,
                                           max_tokens=8)
    beside = lanes.create_chat_completions([SHORT, LONG], temperature=0.0,
                                           max_tokens=8)
    assert _text(beside[0]) == _text(alone)
    assert beside[0]["usage"] == alone["usage"]


def _overflow_queues(serial, lanes):
    n = 2 * LANES + 1
    outs = lanes.create_chat_completions(
        [[{"role": "user", "content": f"caller {i}"}] for i in range(n)],
        temperature=0.0, max_tokens=4)
    assert len(outs) == n
    assert all(o["usage"]["completion_tokens"] >= 1 for o in outs)
    # every lane is given back (the gauge is the scheduler's, a pass behind)
    deadline = time.time() + 30
    while lanes.scheduler_stats()["lanes_live"] and time.time() < deadline:
        time.sleep(0.05)
    assert lanes.scheduler_stats()["lanes_live"] == 0


def _an_oversized_prompt_fails_alone(serial, lanes):
    huge = [{"role": "user", "content": "word " * (4 * N_CTX)}]
    outs = lanes.create_chat_completions([huge, SHORT], temperature=0.0,
                                         max_tokens=4)
    assert outs[0]["error"]["type"] == "invalid_request_error"
    assert str(N_CTX) in outs[0]["error"]["message"]
    # the neighbour is served whole (a tiny random model may stop at once)
    alone, = lanes.create_chat_completions([SHORT], temperature=0.0,
                                           max_tokens=4)
    assert outs[1]["object"] == "chat.completion"
    assert outs[1]["usage"] == alone["usage"]


def _a_prompt_of(eng, low: int, high: int):
    """(messages, n): a prompt of ``low`` < n < ``high`` tokens under the
    engine's own tokenizer and template."""
    for k in range(1, 4 * high):
        msgs = [{"role": "user", "content": "la " * k}]
        n = len(eng.tokenize_messages(msgs))
        if low < n < high:
            return msgs, n
        assert n < high, (k, n)
    raise AssertionError("no such prompt")


def _a_long_neighbour_does_not_truncate_a_short_one(serial, lanes):
    """A lane's budget is its own: beside a prompt that ends a few tokens
    short of the context, the short prompt still decodes its whole budget
    (what it decodes alone)."""
    near_end, n_long = _a_prompt_of(serial, N_CTX - 24, N_CTX - 4)
    alone, = lanes.create_chat_completions([SHORT], temperature=0.0,
                                           max_tokens=12)
    outs = lanes.create_chat_completions([near_end, SHORT], temperature=0.0,
                                         max_tokens=12)
    assert outs[0]["usage"]["completion_tokens"] <= N_CTX - n_long
    assert outs[1]["usage"] == alone["usage"]
    assert _text(outs[1]) == _text(alone)


@pytest.mark.parametrize("which", sorted(FILES))
@pytest.mark.parametrize("holds", [
    _order_and_shapes, _a_batch_of_one_is_the_serial_engines_text,
    _padding_invariance, _overflow_queues, _an_oversized_prompt_fails_alone,
    _a_long_neighbour_does_not_truncate_a_short_one],
    ids=lambda f: f.__name__.lstrip("_"))
def test_a_batch_through_the_lane_engine(pairs, which, holds):
    holds(*pairs(which))


# ---------------------------------------------------------------------------
# the lane engine against the serial one, at one lane and at four
# ---------------------------------------------------------------------------

CTX_KW = dict(n_ctx=512, decode_chunk=4, max_gen_tokens=16,
              prefill_buckets=(32, 128, 512), prefill_chunk=32)
MSGS = [{"role": "user", "content": "Say something."}]


@pytest.fixture(scope="module")
def dense_path(tmp_path_factory):
    path = str(tmp_path_factory.mktemp("parity") / "tiny.gguf")
    testing.write_tiny_llama_gguf(path)
    return path


@pytest.fixture(scope="module")
def serial_512(dense_path):
    return Engine(dense_path, prefix_cache=False, **CTX_KW)


@pytest.fixture(scope="module", params=[1, 4], ids=["lanes-1", "lanes-4"])
def lanes_512(request, dense_path):
    eng = ContinuousEngine(dense_path, batch_size=request.param, **CTX_KW)
    yield eng
    eng.shutdown()


def test_greedy_parity_with_the_serial_engine(serial_512, lanes_512):
    a = serial_512.create_chat_completion(MSGS, temperature=0.0, max_tokens=12)
    b = lanes_512.create_chat_completion(MSGS, temperature=0.0, max_tokens=12)
    assert _text(a) == _text(b) and a["usage"] == b["usage"]


def test_stream_parity_with_the_serial_engine(serial_512, lanes_512):
    def streamed(eng):
        chunks = list(eng.create_chat_completion(
            MSGS, stream=True, temperature=0.0, max_tokens=12))
        text = "".join(c["choices"][0]["delta"].get("content", "")
                       for c in chunks)
        return text, chunks[-1]["choices"][0]["finish_reason"]

    whole = lanes_512.create_chat_completion(MSGS, temperature=0.0,
                                             max_tokens=12)
    assert streamed(lanes_512) == streamed(serial_512)
    assert streamed(lanes_512)[0] == _text(whole)


def test_long_context_generation_matches_the_serial_engine(serial_512,
                                                           lanes_512):
    """A prompt of several slices, most of the context: the lanes' sliced
    admission and the serial engine's prefill give the same greedy text."""
    pages, n = _a_prompt_of(serial_512, 300, 480)
    a = serial_512.create_chat_completion(pages, temperature=0.0,
                                          max_tokens=8)
    b = lanes_512.create_chat_completion(pages, temperature=0.0, max_tokens=8)
    assert a["usage"]["prompt_tokens"] == b["usage"]["prompt_tokens"] == n
    assert _text(a) == _text(b)


# ---------------------------------------------------------------------------
# the deployments under benchmarks/ (read only) and the server's factory
# ---------------------------------------------------------------------------

def _deployments() -> list:
    """Every file under benchmarks/configs and benchmarks/rehearsal that
    describes a deployment (has ``serve.env``)."""
    out = []
    for pattern in ("configs/*.json", "rehearsal/*.json"):
        for path in sorted(glob.glob(os.path.join(REPO, "benchmarks",
                                                  pattern))):
            with open(path) as f:
                doc = json.load(f)
            if isinstance(doc, dict) and "env" in doc.get("serve", {}):
                out.append(pytest.param(doc["serve"]["env"],
                                        id=os.path.basename(path)[:-5]))
    return out


DEPLOYMENTS = _deployments()


def test_the_benchmark_has_its_deployments():
    assert len([d for d in DEPLOYMENTS if "tiny-" not in d.id]) >= 11
    assert len(DEPLOYMENTS) >= 20


@pytest.mark.parametrize("env", DEPLOYMENTS)
def test_a_deployment_sets_only_knobs_the_registry_declares(env):
    from llama_fastapi_k8s_gpu_tpu.utils.config import KNOBS

    lfkt = {k for k in env if k.startswith("LFKT_")}
    assert lfkt, env
    assert lfkt <= set(KNOBS), lfkt - set(KNOBS)


@pytest.mark.parametrize("env", DEPLOYMENTS)
def test_the_factory_picks_the_engine_from_the_lanes_alone(env, monkeypatch):
    """``server/app.py _build_engine`` under the deployment's environment:
    ``Engine`` for one lane, ``ContinuousEngine`` otherwise, with no file
    loaded (the classes are stood in for)."""
    from llama_fastapi_k8s_gpu_tpu import engine as engines
    from llama_fastapi_k8s_gpu_tpu.server import app as server
    from llama_fastapi_k8s_gpu_tpu.utils.config import get_settings

    built = []

    def stand_in(name):
        def make(path, **kw):
            built.append((name, path, kw))
            return name
        return make

    monkeypatch.setattr(engines, "Engine", stand_in("Engine"))
    monkeypatch.setattr(engines, "ContinuousEngine",
                        stand_in("ContinuousEngine"))
    for key, value in env.items():
        monkeypatch.setenv(key, value)
    settings = get_settings()
    lanes = int(env.get("LFKT_BATCH_SIZE", "1"))
    assert settings.batch_size == lanes
    got = server._build_engine(settings, "some.gguf",
                               server._base_engine_kwargs(settings))
    (name, path, kw), = built
    assert got == name == ("ContinuousEngine" if lanes > 1 else "Engine")
    assert path == "some.gguf"
    assert kw["n_ctx"] == int(env["LFKT_MAX_CONTEXT_TOKENS"])
    assert kw.get("batch_size", 1) == lanes
    if "LFKT_PREFILL_CHUNK" in env:
        assert kw["prefill_chunk"] == int(env["LFKT_PREFILL_CHUNK"])


def test_an_undeclared_variable_changes_no_setting(monkeypatch):
    """Not declared, not read: the settings are the defaults' whatever an
    ``LFKT_*`` variable without a row in ``KNOBS`` says (docs/CONFIG.md
    says so to operators)."""
    from llama_fastapi_k8s_gpu_tpu.utils.config import (
        KNOBS, get_settings, knob)

    name = "LFKT_NO_SUCH_KNOB"
    assert name not in KNOBS
    before = get_settings()
    monkeypatch.setenv(name, "4")
    assert get_settings() == before
    with pytest.raises(KeyError):
        knob(name)


# ---------------------------------------------------------------------------
# the arrows of the package
# ---------------------------------------------------------------------------

def _imports(package: str) -> set:
    """The package's sub-packages that the files under ``package`` import
    (absolute or relative, at any depth of the file: a layer body's import
    counts)."""
    found = set()
    top = os.path.basename(PACKAGE)
    for root, _, files in os.walk(os.path.join(PACKAGE, package)):
        for name in files:
            if not name.endswith(".py"):
                continue
            path = os.path.join(root, name)
            here = os.path.relpath(path, PACKAGE).split(os.sep)[:-1]
            with open(path) as f:
                tree = ast.parse(f.read())
            for node in ast.walk(tree):
                mods = []
                if isinstance(node, ast.Import):
                    mods = [a.name.split(".") for a in node.names]
                elif isinstance(node, ast.ImportFrom):
                    parts = (node.module or "").split(".") \
                        if node.module else []
                    if node.level:
                        base = here[:len(here) - (node.level - 1)]
                        mods = [[top] + base + parts]
                        if not parts:       # ``from .. import x``
                            mods = [[top] + base + [a.name]
                                    for a in node.names]
                    else:
                        mods = [parts]
                for m in mods:
                    if m[:1] == [top] and len(m) > 1:
                        found.add(m[1])
    return found - {package}


@pytest.mark.parametrize("package, never", [
    ("models", {"parallel", "engine", "server", "serving"}),
    ("ops", {"parallel", "engine", "server", "serving"}),
    ("parallel", {"engine", "server", "serving"}),
    ("engine", {"server"}),
])
def test_the_arrows_point_down(package, never):
    """server -> engine -> parallel -> models -> ops: a layer body that
    reaches up for a mesh (as ``models/llama.py`` once did for ring
    attention over chips) fails here, by the package's name."""
    assert not _imports(package) & never, _imports(package) & never


def test_the_tree_has_two_engines():
    import llama_fastapi_k8s_gpu_tpu.engine as engines
    import llama_fastapi_k8s_gpu_tpu.parallel as parallel

    assert ContinuousEngine.__bases__ == (Engine,)
    assert Engine.__bases__ == (object,)
    names = {n for n in dir(parallel) if not n.startswith("_")}
    assert {"batched_generate_chunk_perlane_jit", "init_batched_state",
            "init_lane_left"} <= names <= {
        "batched", "kvpool", "batched_generate_chunk_perlane_jit",
        "init_batched_state", "init_lane_left"}
    # the server constructs exactly these two classes
    with open(os.path.join(PACKAGE, "server", "app.py")) as f:
        tree = ast.parse(f.read())
    made = {n.func.id for n in ast.walk(tree)
            if isinstance(n, ast.Call) and isinstance(n.func, ast.Name)
            and n.func.id in dir(engines)}
    assert made - {"Watchdog"} == {"Engine", "ContinuousEngine"}
