"""The cache kind as ONE object (models/cache.py): what each of the five
kinds' ``CACHE`` says of its tiny preset, against what the engines and
``/health`` said of it by hand at f321371 (before the object existed).
``tests/cache_kinds_f321371.json`` holds that commit's own words, taken
from its engines on the tiny files: every refusal's text (re-pinned at PR
60 to the asks that are left: the mesh, sequence-parallel and ``cycle``
refusals went with their engines), which of two refusals is named first, the ``/health`` ``engine.cache`` block and the
``/metrics`` names of a fresh engine's counters.

No engine starts here and nothing is jitted: a kind is functions of the
configuration.  The last tests pin the seam itself: outside ``models/``
nothing tests the kind's NAME, so a sixth kind is one module and one row of
``cache._MODULES``.
"""

from __future__ import annotations

import json
import os
import re
import types

import jax
import jax.numpy as jnp
import pytest

from llama_fastapi_k8s_gpu_tpu import testing
from llama_fastapi_k8s_gpu_tpu.engine.engine import Engine
from llama_fastapi_k8s_gpu_tpu.models import llama
from llama_fastapi_k8s_gpu_tpu.models import cache
from llama_fastapi_k8s_gpu_tpu.models.cache import FEATURES, cache_of

HERE = os.path.dirname(os.path.abspath(__file__))
PACKAGE = os.path.join(os.path.dirname(HERE), "llama_fastapi_k8s_gpu_tpu")
with open(os.path.join(HERE, "cache_kinds_f321371.json")) as f:
    PARENT = json.load(f)

WRITERS = {
    "ring": testing.write_tiny_llama_gguf,
    "window+summaries": testing.write_tiny_evabyte_gguf,
    "state+ring": testing.write_tiny_sala_gguf,
    "latent-ring": testing.write_tiny_mla_gguf,
    "window+global-ring": testing.write_tiny_hybrid_gguf,
}
KINDS = sorted(WRITERS)
ASKS = ("int8", "paged")


@pytest.fixture(scope="module")
def cfgs(tmp_path_factory):
    """Each kind's configuration as an engine reads it from its tiny file
    (``n_ctx`` 256, what the parent's words were taken at)."""
    from llama_fastapi_k8s_gpu_tpu.gguf import GGUFFile
    from llama_fastapi_k8s_gpu_tpu.models.config import ModelConfig

    out = {}
    for kind, write in WRITERS.items():
        path = str(tmp_path_factory.mktemp("kinds") / "tiny.gguf")
        write(path)
        out[kind] = ModelConfig.from_gguf(GGUFFile(path), n_ctx=256)
        assert out[kind].cache_kind == kind == cache_of(out[kind]).name
    return out


def _engine(cfg, **attrs):
    """What ``Engine``'s kind-facing methods need of an engine."""
    return types.SimpleNamespace(cfg=cfg, cache=cache_of(cfg), **attrs)


@pytest.mark.parametrize("kind", KINDS)
def test_the_leaves_weigh_what_nbytes_says(cfgs, kind):
    cfg = cfgs[kind]
    leaves = jax.eval_shape(lambda: cache_of(cfg).init(cfg, jnp.bfloat16))
    nbytes = sum(a.size * a.dtype.itemsize for a in leaves.values())
    assert nbytes == cache_of(cfg).nbytes(cfg) == llama.cache_nbytes(cfg)
    # the names the rest of the tree imports are the same functions
    same = jax.eval_shape(lambda: llama.init_cache(cfg))
    assert jax.tree.structure(same) == jax.tree.structure(leaves)


def test_an_int8_ring_lays_out_its_scales(cfgs):
    import dataclasses

    cfg = dataclasses.replace(cfgs["ring"], kv_dtype="int8")
    leaves = jax.eval_shape(lambda: cache_of(cfg).init(cfg, jnp.bfloat16))
    assert set(leaves) == {"k_q", "v_q", "k_s", "v_s"}
    assert leaves["k_s"].shape == leaves["k_q"].shape[:-1]
    assert sum(a.size * a.dtype.itemsize for a in leaves.values()) \
        == cache_of(cfg).nbytes(cfg)


@pytest.mark.parametrize("ask", ASKS)
@pytest.mark.parametrize("kind", KINDS)
def test_what_a_kind_cannot_serve_is_refused_in_the_parents_words(
        cfgs, kind, ask):
    eng = _engine(cfgs[kind])
    want = PARENT[kind]["refusals"].get(ask)
    asks = {ask: True}
    if want is None:          # the ring serves them all
        assert kind == "ring" and eng.cache.supports[ask] is True
        Engine._refuse_unsupported(eng, asks)
        return
    with pytest.raises(ValueError) as e:
        Engine._refuse_unsupported(eng, asks)
    assert str(e.value) == want


@pytest.mark.parametrize("kind,chunk", [
    ("window+summaries", 48), ("window+summaries", 2), ("state+ring", 12)])
def test_a_slice_the_kind_cannot_take_is_refused_in_the_parents_words(
        cfgs, kind, chunk):
    eng = _engine(cfgs[kind])
    with pytest.raises(ValueError) as e:
        Engine._refuse_unsupported(eng, {"slice": chunk})
    assert str(e.value) == PARENT[kind]["refusals"][f"slice={chunk}"]


@pytest.mark.parametrize("kind", KINDS)
def test_the_slice_its_tests_serve_with_is_taken(cfgs, kind):
    chunk = 8 if kind == "state+ring" else 16
    Engine._refuse_unsupported(_engine(cfgs[kind]), {"slice": chunk})


@pytest.mark.parametrize("kind", [k for k in KINDS if k != "ring"])
def test_of_two_refusals_the_parents_first_is_named(cfgs, kind):
    eng = _engine(cfgs[kind])
    for pair, want in PARENT[kind]["named_first"].items():
        asks = dict.fromkeys(pair.split("+"), True)
        with pytest.raises(ValueError) as e:
            Engine._refuse_unsupported(eng, asks)
        assert str(e.value) == want, pair
    assert set(ASKS) | {"slice"} == set(FEATURES)


@pytest.mark.parametrize("kind", KINDS)
def test_health_is_the_parents_block(cfgs, kind):
    eng = _engine(cfgs[kind], _prefix_cache=True, template_kind="mistral",
                  _template_named=True)
    assert Engine.cache_kind.fget(eng) == PARENT[kind]["health"]
    if kind == "latent-ring":
        # a lane engine's own switch speaks before the serial one's
        eng._lane_prefix = False
        assert Engine.cache_kind.fget(eng) \
            == PARENT[kind]["health_reuse_off"]


@pytest.mark.parametrize("kind", KINDS)
def test_rolls_back_is_what_health_says_of_prefix_reuse(cfgs, kind):
    """The one property prefix reuse and lane claims ask of a cache."""
    cache = cache_of(cfgs[kind])
    said = (PARENT[kind]["health"] or {"prefix_reuse": "on"})["prefix_reuse"]
    assert cache.rolls_back == (said == "on")
    assert cache.rolls_back or said.startswith("off: ")
    assert cache.always_slices == (kind != "ring")


@pytest.mark.parametrize("kind", KINDS)
def test_the_counters_names_are_the_parents_and_the_catalogs(cfgs, kind):
    from llama_fastapi_k8s_gpu_tpu.obs.catalog import METRICS

    cache = cache_of(cfgs[kind])
    counts = cache.new_counts()
    gauges = cache.gauges(counts)
    # (since PR 50 a latent ring also counts its prefill slices by read)
    since = ["latent_slices_kernel_total", "latent_slices_loop_total"] \
        if kind == "latent-ring" else []
    assert sorted(gauges) == sorted(PARENT[kind]["gauges"] + since)
    assert all(v == 0 for v in gauges.values())
    assert {name.partition("{")[0] for name in gauges} <= set(METRICS)
    # a chunk and a prompt count into the keys ``new_counts`` made
    cache.note_decode(counts, cfgs[kind], [3, 70], 4, [3, 70, 90])
    cache.note_prefill(counts, cfgs[kind], 100, [(0, 64), (64, 16)])
    assert set(counts) == set(cache.new_counts())
    assert any(cache.gauges(counts).values())


@pytest.mark.parametrize("kind", KINDS)
def test_a_traced_request_says_what_the_kind_adds(cfgs, kind):
    cache, cfg = cache_of(cfgs[kind]), cfgs[kind]
    traced = cache.note_prefill(cache.new_counts(), cfg, 100,
                                [(0, 64), (64, 16), (80, 16), (96, 16)])
    untraced = cache.note_prefill(cache.new_counts(), cfg, 100, None)
    assert sorted(traced) == {
        "ring": [], "window+global-ring": [],
        "window+summaries": ["windows_closed"],
        "state+ring": ["kc_closed", "sparse_positions"],
        "latent-ring": ["cache", "latent_positions_read", "latent_read"]}[kind]
    assert kind == "latent-ring" or untraced == traced
    assert cache.decode_span_attrs(41) == (
        {"cache": "latent-ring", "latent_positions": 41}
        if kind == "latent-ring" else {})


@pytest.mark.parametrize("module", sorted(cache._MODULES.values()))
def test_a_kind_answers_int8_and_paging_and_nothing_else(module):
    """Every kind's module (the sixth, ``lfm2``, has no tiny preset above;
    ``mla`` has a second object for the indexed variant): ``supports``
    answers exactly the asks an engine can make beside its slice: no row
    for a mesh, a ring sharded over chips or a second scheduler (PR 60)."""
    import importlib

    mod = importlib.import_module(
        "llama_fastapi_k8s_gpu_tpu.models." + module)
    kinds = [mod.CACHE] + [getattr(mod, n) for n in ("INDEXED",)
                           if hasattr(mod, n)]
    for kind in kinds:
        assert set(kind.supports) == {"int8", "paged"} \
            == set(FEATURES) - {"slice"}
        assert all(v is True or (isinstance(v, str) and v)
                   for v in kind.supports.values())
        assert not hasattr(kind, "shardings")


# ---------------------------------------------------------------------------
# the seam
# ---------------------------------------------------------------------------

def _hits(pattern: str, *dirs: str, skip=()) -> list:
    """The package's source lines under ``dirs`` that ``pattern`` finds."""
    rx, out = re.compile(pattern), []
    for d in dirs:
        for root, _, files in os.walk(os.path.join(PACKAGE, d)):
            for name in files:
                path = os.path.join(root, name)
                rel = os.path.relpath(path, PACKAGE)
                if not name.endswith(".py") or rel in skip:
                    continue
                with open(path) as f:
                    out += [f"{rel}:{n}: {line.strip()}"
                            for n, line in enumerate(f, 1)
                            if rx.search(line)]
    return out


def test_nothing_outside_models_tells_the_kinds_apart():
    """A seventh ``if`` on the kind's name fails here, not in review."""
    outside = ("engine", "parallel", "server", "serving", "obs")
    assert not _hits(r"cache_kind\s*(==|!=|in\b|not in\b)|\.eva_window",
                     *outside)
    assert not _hits(r"\b(RING|WINDOW_SUMMARIES|STATE_RING|LATENT_RING|"
                     r"WINDOW_GLOBAL_RING)\b", *outside)
    assert not _hits(r"models import .*\b(eva|sala|mla|hybrid)\b|"
                     r"models\.(eva|sala|mla|hybrid)\b", *outside)
    # in models/ the name is DECIDED in config.py and MAPPED in cache.py
    assert not _hits(r"cache_kind\s*(==|!=|in\b|not in\b)", "models")
    # the window's size is a size in its own files; llama.py's one layer
    # body asks once which attention a layer calls (ROADMAP C7)
    assert len(_hits(r"\.eva_window", "models", skip=(
        "models/eva.py", "models/config.py", "models/params.py"))) == 1


def test_the_per_kind_methods_and_counter_dicts_are_gone():
    assert not _hits(r"def _refuse_for_", ".")
    assert not _hits(r"\.(ring_slots|eva_counts|sala_counts|hybrid_counts|"
                     r"ring_rows_written)\b", ".")
    from llama_fastapi_k8s_gpu_tpu.models.config import ModelConfig

    assert not hasattr(ModelConfig, "widest_slice")
    assert not hasattr(Engine, "_note_prefill_windows")
