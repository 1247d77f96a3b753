"""The executable store (utils/execstore.py ``StoredJit``, obs/devtime.py ``_TimedJit``; PR 55).

Tiny programs on the CPU, seconds in all.  What is pinned: a second
registry on the same directory LOADS and does not trace; the loaded
program's results are bit-equal to the jit path's and donation holds; the
key moves with everything a trace reads; a file that does not load is
counted, deleted and rebuilt; a directory that cannot be written turns the
store off; a program under the floor is not stored and stays on the jit
path; a first-seen signature is a compile event however its executable
came to be.
"""

import dataclasses
import functools
import os
import subprocess
import sys
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from llama_fastapi_k8s_gpu_tpu.models.config import ModelConfig
from llama_fastapi_k8s_gpu_tpu.obs.devtime import DevtimeRegistry
from llama_fastapi_k8s_gpu_tpu.utils import execstore
from llama_fastapi_k8s_gpu_tpu.utils.execstore import ExecStore

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CFG = ModelConfig(vocab_size=32, dim=8, n_layers=2, n_heads=2, n_kv_heads=2,
                  ffn_dim=16, n_ctx=64)


def registry(path, floor_s=0.0) -> DevtimeRegistry:
    """A private registry on ``path``; ``floor_s`` 0 stores the tiny
    programs a CPU builds in milliseconds."""
    return DevtimeRegistry(armed=True, budget=8, stamps=False,
                           store=ExecStore(str(path), floor_s=floor_s))


def chunk_program(traces: list):
    """A fresh jit (its own jit cache) shaped like the decode chunk: a scan
    with a static configuration and step count, dict arguments, a donated
    carry; ``traces`` grows whenever its Python body runs.  It closes over
    ``traces``, so every wrap below passes ``key=KEY``."""

    @functools.partial(jax.jit, static_argnames=("cfg", "n_steps"),
                       donate_argnames=("state",))
    def chunk(params, cfg, state, st, n_steps, scale=2.0):
        traces.append(cfg.dim)

        def step(c, _):
            a = c["a"] * params["w"] + st["t"] * scale + cfg.dim
            return {"a": a, "pos": c["pos"] + 1}, a.sum()

        return jax.lax.scan(step, state, None, length=n_steps)

    return chunk


KEY = ("tests/test_execstore.py",)


def chunk_args(n=4):
    return ({"w": jnp.linspace(0.5, 1.5, n)}, CFG,
            {"a": jnp.arange(n, dtype=jnp.float32), "pos": jnp.int32(0)},
            {"t": jnp.float32(0.25)})


def files(path) -> list[str]:
    return sorted(f for f in os.listdir(path) if f.endswith(execstore.SUFFIX))


# -- load, do not trace -------------------------------------------------------

def test_a_second_registry_loads_and_does_not_trace(tmp_path):
    built, loaded, plain = [], [], []
    want = chunk_program(plain)(*chunk_args(), n_steps=3)

    first = registry(tmp_path).timed_jit("chunk", chunk_program(built), key=KEY)
    params, cfg, state, st = chunk_args()
    got = first(params, cfg, state, st, n_steps=3)
    assert built == [8] and len(files(tmp_path)) == 1
    assert state["a"].is_deleted()          # donated through the Compiled
    totals = first._reg.store_totals()
    assert (totals["programs_built"], totals["programs_loaded"]) == (1, 0)

    reg = registry(tmp_path)
    second = reg.timed_jit("chunk", chunk_program(loaded), key=KEY)
    params, cfg, state, st = chunk_args()
    again = second(params, cfg, state, st, n_steps=3)
    assert loaded == []                     # the body never ran
    assert state["a"].is_deleted()
    for a, b, c in zip(jax.tree.leaves(want), jax.tree.leaves(got),
                       jax.tree.leaves(again)):
        assert np.array_equal(a, b) and np.array_equal(a, c)
        assert a.dtype == b.dtype == c.dtype
    totals = reg.store_totals()
    assert totals["programs_loaded"] == 1 and totals["programs_built"] == 0
    assert totals["load_failures"] == 0 and totals["load_s"] > 0
    # steady state: the same Compiled, no file, no trace, every call counted
    for _ in range(3):
        params, cfg, state, st = chunk_args()
        second(params, cfg, state, st, n_steps=3)
    c = reg.counters()["chunk"]
    assert (c["compiles"], c["dispatches"], c["signatures"]) == (1, 4, 1)
    assert loaded == [] and second.__wrapped__._cache_size() == 0
    snap = reg.snapshot()
    prog = next(p for p in snap["programs"] if p["name"] == "chunk")
    assert (prog["loaded"], prog["built"]) == (1, 0)
    assert prog["load_seconds_total"] == prog["compile_seconds_total"] > 0
    assert snap["executable_store"]["on"] and \
        snap["executable_store"]["files"] == 1


def test_an_unseen_signature_is_a_compile_event_loaded_or_built(tmp_path):
    """What ``compiles_in_window`` reads: the registry's compile count
    rises on a signature first seen, whether its executable is built or
    loaded."""
    for n_files, how in ((2, "built"), (2, "loaded")):
        reg = registry(tmp_path)
        fn = reg.timed_jit("chunk", chunk_program([]), key=KEY)
        fn(*chunk_args(), n_steps=3)
        assert reg.counters()["chunk"]["compiles"] == 1
        cursor, _ = reg.events_since(0)
        fn(*chunk_args(), n_steps=5)        # inside "the window"
        c = reg.counters()["chunk"]
        assert (c["compiles"], c["signatures"]) == (2, 2)
        _, events = reg.events_since(cursor)
        assert [e["how"] for e in events] == [how]
        assert len(files(tmp_path)) == n_files


def test_a_static_value_selects_its_own_executable(tmp_path):
    """Two configurations through one wrapper, then through a second
    registry: each call gets the executable of ITS static values."""
    other = dataclasses.replace(CFG, dim=16)
    outs = []
    for _ in range(2):
        fn = registry(tmp_path).timed_jit("chunk", chunk_program([]), key=KEY)
        params, _, state, st = chunk_args()
        a = fn(params, CFG, state, st, n_steps=2)[1]
        params, _, state, st = chunk_args()
        b = fn(params, other, state, st, n_steps=2)[1]
        assert not np.array_equal(a, b)
        outs.append((np.asarray(a), np.asarray(b)))
    assert np.array_equal(outs[0][0], outs[1][0])
    assert np.array_equal(outs[0][1], outs[1][1])


# -- the key ------------------------------------------------------------------

def key_of(tmp_path, *, cfg=CFG, n=4, sharding=None, n_steps=3) -> str:
    fn = registry(tmp_path).timed_jit("chunk", chunk_program([]), key=KEY)
    params, _, state, st = chunk_args(n)
    if sharding is not None:
        params = jax.device_put(params, sharding)
    return execstore.program_key(
        "chunk", fn._prog._info, KEY, (params, cfg, state, st),
        {"n_steps": n_steps}, fn._prog._static_nums, fn._prog._static_names,
        fn._reg.degrades())


def _mesh_sharding():
    mesh = Mesh(np.array(jax.devices()[:2]), ("tp",))
    return NamedSharding(mesh, P("tp"))


MOVES = {
    "a ModelConfig field": lambda mp: {"cfg": dataclasses.replace(
        CFG, rope_theta=10000.0)},
    "a static step count": lambda mp: {"n_steps": 4},
    "an LFKT_ variable": lambda mp: mp.setenv("LFKT_Q4K_VARIANT", "b") or {},
    "XLA_FLAGS": lambda mp: mp.setenv(
        "XLA_FLAGS", os.environ.get("XLA_FLAGS", "") + " --xla_x=1") or {},
    "a probe verdict": lambda mp: mp.setitem(
        __import__("llama_fastapi_k8s_gpu_tpu.ops.pallas.probe",
                   fromlist=["_VERDICTS"])._VERDICTS,
        "probe_fused_q4k", "MosaicError: no") or {},
    "the source hash": lambda mp: mp.setattr(
        execstore, "source_hash", lambda: "0" * 16) or {},
    "an argument's shape": lambda mp: {"n": 8},
    "an argument's sharding": lambda mp: {"sharding": _mesh_sharding()},
    "the jax version": lambda mp: mp.setattr(jax, "__version__", "0.0.1")
    or {},
}


@pytest.mark.parametrize("what", sorted(MOVES))
def test_the_key_changes_with(what, tmp_path, monkeypatch):
    base = key_of(tmp_path)
    assert key_of(tmp_path) == base          # and with nothing else
    moved = key_of(tmp_path, **MOVES[what](monkeypatch))
    assert moved != base
    changed = [(a, b) for a, b in zip(base.split("\n"), moved.split("\n"))
               if a != b]
    assert changed, "the keys differ in length alone"


def test_another_name_for_the_same_placement_is_the_same_signature(tmp_path):
    """On one device ``P()``, ``P(None, None)``, ``P('dp', None)`` and a
    ``SingleDeviceSharding`` are one placement: one key, one file, one
    compile event (tests/test_devtime.py has the jit path's twin), and an
    uncommitted array in that placement is no other signature either."""
    from jax.sharding import SingleDeviceSharding

    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("dp", "tp"))
    names = [NamedSharding(mesh, P()), NamedSharding(mesh, P(None, None)),
             NamedSharding(mesh, P("dp", None)),
             SingleDeviceSharding(jax.devices()[0])]
    traces = []

    def double(x):
        traces.append(1)
        return x * 2

    reg = registry(tmp_path)
    fn = reg.timed_jit("double", jax.jit(double), key=KEY)
    for s in names:
        out = fn(jax.device_put(jnp.ones((4, 4)), s))
        assert float(out[0, 0]) == 2.0
    fn(jnp.ones((4, 4)))                    # uncommitted, the same device
    c = reg.counters()["double"]
    assert (c["compiles"], c["dispatches"]) == (1, 5)
    assert traces == [1] and len(files(tmp_path)) == 1 and len(fn._prog._runs) == 1


def test_where_the_process_listens_is_not_in_the_key(tmp_path, monkeypatch):
    base = key_of(tmp_path)
    for name, value in (("LFKT_PORT", "18231"), ("LFKT_HOST", "0.0.0.0"),
                        ("LFKT_MODEL_DIR", "/elsewhere"),
                        ("LFKT_TRACE_SAMPLE", "1")):
        monkeypatch.setenv(name, value)
    assert key_of(tmp_path) == base


def test_a_degrade_is_in_the_key(tmp_path):
    reg = registry(tmp_path)
    fn = reg.timed_jit("chunk", chunk_program([]), key=KEY)
    params, cfg, state, st = chunk_args()

    def key():
        return execstore.program_key(
            "chunk", fn._prog._info, KEY, (params, cfg, state, st),
            {"n_steps": 3}, fn._prog._static_nums, fn._prog._static_names,
            reg.degrades())

    base = key()
    reg.record_degrade("flash_attention_decode", "MosaicError: no")
    assert key() != base


def test_the_key_is_the_same_in_another_process(tmp_path):
    """No ``hash()``, no address: a child with another hash seed builds,
    a second child with a third seed loads and never traces."""
    script = f"""
import sys
sys.path.insert(0, {ROOT!r}); sys.path.insert(0, {ROOT + '/tests'!r})
import conftest, test_execstore as t
traces = []
reg = t.registry({str(tmp_path)!r})
out = reg.timed_jit("chunk", t.chunk_program(traces), key=t.KEY)(
    *t.chunk_args(), n_steps=3)
print("RESULT", len(traces), reg.store_totals()["programs_loaded"],
      float(out[1].sum()))
"""
    seen = []
    for seed in ("1", "2"):
        r = subprocess.run([sys.executable, "-c", script], text=True,
                           capture_output=True, timeout=120,
                           env={**os.environ, "PYTHONHASHSEED": seed})
        assert r.returncode == 0, r.stderr[-2000:]
        seen.append(next(line.split()[1:] for line in r.stdout.splitlines()
                         if line.startswith("RESULT")))
    assert seen[0][:2] == ["1", "0"] and seen[1][:2] == ["0", "1"]
    assert seen[0][2] == seen[1][2]


def test_a_closure_is_stored_only_under_a_key_of_its_own(tmp_path):
    def factory(scale, key=None, traces=None):
        def fn(x):
            traces.append(scale)
            return x * scale
        return registry(tmp_path).timed_jit("scaled", jax.jit(fn), key=key)

    t = []
    for _ in range(2):                      # no key: never stored
        factory(2.0, traces=t)(jnp.ones(3))
    assert t == [2.0, 2.0] and files(tmp_path) == []
    t = []
    outs = [factory(s, key=("scaled", s), traces=t)(jnp.ones(3))
            for s in (2.0, 3.0, 2.0, 3.0)]
    assert t == [2.0, 3.0] and len(files(tmp_path)) == 2
    assert [float(o[0]) for o in outs] == [2.0, 3.0, 2.0, 3.0]


def test_a_static_the_store_cannot_write_down_stays_on_the_jit_path(tmp_path):
    class Opaque:
        def __hash__(self):
            return 7

        def __eq__(self, other):
            return isinstance(other, Opaque)

    traces = []

    @functools.partial(jax.jit, static_argnames=("how",))
    def fn(x, how):
        traces.append(1)
        return x + 1

    for _ in range(2):
        reg = registry(tmp_path)
        out = reg.timed_jit("opaque", fn)(jnp.ones(2), how=Opaque())
        assert float(out[0]) == 2.0
    assert files(tmp_path) == [] and traces == [1]     # one jit cache
    assert reg.store_totals()["programs_built"] == 0


# -- failure is never fatal ---------------------------------------------------

@pytest.mark.parametrize("damage", ["truncated", "garbage", "another-key"])
def test_a_file_that_does_not_load_is_counted_deleted_and_rebuilt(
        tmp_path, damage):
    registry(tmp_path).timed_jit("chunk", chunk_program([]), key=KEY)(
        *chunk_args(), n_steps=3)
    (name,) = files(tmp_path)
    path = tmp_path / name
    blob = path.read_bytes()
    if damage == "truncated":
        path.write_bytes(blob[:len(blob) // 2])
    elif damage == "garbage":
        path.write_bytes(b"not an executable")
    else:       # a sound record of some other key under this file's name
        registry(tmp_path).timed_jit("chunk", chunk_program([]), key=KEY)(
            *chunk_args(), n_steps=5)
        (other,) = [f for f in files(tmp_path) if f != name]
        path.write_bytes((tmp_path / other).read_bytes())

    traces = []
    reg = registry(tmp_path)
    want = chunk_program([])(*chunk_args(), n_steps=3)
    got = reg.timed_jit("chunk", chunk_program(traces), key=KEY)(
        *chunk_args(), n_steps=3)
    assert np.array_equal(want[1], got[1]) and traces == [8]
    totals = reg.store_totals()
    assert totals["load_failures"] == 1 and totals["programs_built"] == 1
    assert name in files(tmp_path) and path.read_bytes() != b"not an executable"

    traces = []                             # and the rebuilt file loads
    reg = registry(tmp_path)
    reg.timed_jit("chunk", chunk_program(traces), key=KEY)(*chunk_args(), n_steps=3)
    assert traces == [] and reg.store_totals()["load_failures"] == 0


def test_a_payload_that_does_not_deserialize_is_rebuilt(tmp_path, monkeypatch):
    """Another runtime: the record reads, the executable does not load."""
    registry(tmp_path).timed_jit("chunk", chunk_program([]), key=KEY)(
        *chunk_args(), n_steps=3)

    def refuse(rec):
        raise RuntimeError("executable built by another libtpu")

    monkeypatch.setattr(execstore, "_load", refuse)
    traces = []
    reg = registry(tmp_path)
    reg.timed_jit("chunk", chunk_program(traces), key=KEY)(*chunk_args(), n_steps=3)
    assert traces == [8] and len(files(tmp_path)) == 1
    totals = reg.store_totals()
    assert (totals["load_failures"], totals["programs_built"]) == (1, 1)


def test_a_directory_that_cannot_be_made_turns_the_store_off(tmp_path, caplog):
    (tmp_path / "file").write_text("in the way")
    with caplog.at_level("WARNING"):
        reg = registry(tmp_path / "file" / "executables")
    assert reg.store.off and "executable store off" in caplog.text
    traces = []
    fn = reg.timed_jit("chunk", chunk_program(traces), key=KEY)
    for _ in range(2):
        fn(*chunk_args(), n_steps=3)
    assert traces == [8] and fn._prog.on_jit             # the jit, for good
    assert reg.counters()["chunk"]["compiles"] == 1
    assert reg.snapshot()["executable_store"]["on"] is False


def test_a_directory_that_cannot_be_written_turns_the_store_off(
        tmp_path, monkeypatch, caplog):
    """A read-only volume (the tests run as root, whom no mode stops: the
    refusal is the temporary file's)."""
    def read_only(*a, **k):
        raise PermissionError(30, "Read-only file system")

    monkeypatch.setattr(tempfile, "mkstemp", read_only)
    reg = registry(tmp_path)
    fn = reg.timed_jit("chunk", chunk_program([]), key=KEY)
    with caplog.at_level("WARNING"):
        want = fn(*chunk_args(), n_steps=3)[1]
        fn(*chunk_args(), n_steps=5)
    assert caplog.text.count("executable store off") == 1   # one line
    assert reg.store.off and files(tmp_path) == []
    assert np.array_equal(want, chunk_program([])(*chunk_args(), n_steps=3)[1])
    assert reg.counters()["chunk"]["compiles"] == 2


def test_files_of_another_source_hash_are_pruned_at_a_write(
        tmp_path, monkeypatch):
    registry(tmp_path).timed_jit("chunk", chunk_program([]), key=KEY)(
        *chunk_args(), n_steps=3)
    (old,) = files(tmp_path)
    (tmp_path / "notes.txt").write_text("not the store's")
    monkeypatch.setattr(execstore, "source_hash", lambda: "f" * 16)
    reg = registry(tmp_path)
    reg.timed_jit("chunk", chunk_program([]), key=KEY)(*chunk_args(), n_steps=3)
    (new,) = files(tmp_path)
    assert new != old and new.startswith("f" * 16)
    assert (tmp_path / "notes.txt").exists()
    assert reg.snapshot()["executable_store"]["pruned"] == 1


# -- which programs -----------------------------------------------------------

@pytest.mark.parametrize("armed", [True, False])
def test_arming_the_registry_changes_no_dispatch(tmp_path, armed):
    """What dispatches a call is decided before ``_armed`` is read: a
    disarmed registry loads and dispatches through the store all the same,
    and counts nothing."""
    registry(tmp_path).timed_jit("chunk", chunk_program([]), key=KEY)(
        *chunk_args(), n_steps=3)
    traces = []
    reg = registry(tmp_path)
    reg.configure(armed=armed)
    fn = reg.timed_jit("chunk", chunk_program(traces), key=KEY)
    want = chunk_program([])(*chunk_args(), n_steps=3)[1]
    for _ in range(2):
        assert np.array_equal(fn(*chunk_args(), n_steps=3)[1], want)
    assert traces == [] and fn.__wrapped__._cache_size() == 0   # loaded
    assert not fn._prog.on_jit and len(fn._prog._runs) == 1
    c = reg.counters()["chunk"]
    assert (c["compiles"], c["dispatches"]) == ((1, 2) if armed else (0, 0))
    assert reg.store_totals()["programs_loaded"] == (1 if armed else 0)


def test_a_program_under_the_floor_is_not_stored(tmp_path):
    """The default floor, 0.5 s: a tiny program's build writes nothing,
    the program stays on the jit in this process and the next."""
    traces = []
    for start in range(2):
        reg = registry(tmp_path, floor_s=execstore.FLOOR_S)
        fn = reg.timed_jit("small", chunk_program(traces), key=KEY)
        for _ in range(3):
            fn(*chunk_args(), n_steps=3)
        assert fn._prog.on_jit
        c = reg.counters()["small"]
        assert (c["compiles"], c["dispatches"]) == (1, 3)
        totals = reg.store_totals()
        assert totals["programs_built"] == totals["programs_loaded"] == 0
        assert fn.__wrapped__._cache_size() == 1        # the jit served
    assert traces == [8, 8]                 # traced at each start, as ever
    assert files(tmp_path) == []


def test_without_a_store_the_wrapper_is_the_jit(tmp_path):
    reg = DevtimeRegistry(armed=True, budget=8, stamps=False)
    traces = []
    fn = reg.timed_jit("chunk", chunk_program(traces), key=KEY)
    for _ in range(2):
        fn(*chunk_args(), n_steps=3)
    assert traces == [8] and fn.__wrapped__._cache_size() == 1
    assert reg.snapshot()["executable_store"] is None
    assert reg.store_totals() == {
        "programs_loaded": 0, "load_s": 0.0, "programs_built": 0,
        "build_s": 0.0, "load_failures": 0}


def test_the_weights_part_of_the_key_is_held_by_identity(tmp_path):
    """A tree of many leaves is flattened once, not at every call, and the
    memo holds no strong reference to it."""
    @jax.jit
    def total(params, x):
        return sum(jax.tree.leaves(params)) + x

    reg = registry(tmp_path)
    fn = reg.timed_jit("total", total)
    params = {f"l{i}": jnp.float32(i) for i in range(40)}
    assert float(fn(params, jnp.float32(1))) == sum(range(40)) + 1
    (memo,) = fn._prog._trees.values()
    key = fn._prog._call_key((params, jnp.float32(1)), {})
    assert key[0] == memo[1] and len(fn._prog._runs) == 1
    assert float(fn(params, jnp.float32(2))) == sum(range(40)) + 2
    # other avals under a new identity: their own signature
    wide = {f"l{i}": jnp.ones((2,)) for i in range(40)}
    fn(wide, jnp.float32(1))
    assert len(fn._prog._runs) == 2
    first = params["l0"]
    del params, first
    assert memo[0]() is None                # the leaf died with its tree


def test_a_tree_made_anew_at_every_call_keeps_its_signature(tmp_path):
    """A state of many leaves is another object at every call: the memo of
    identities is emptied when it fills, and no tree's part of the key may
    change for that (a signature would be first seen again: a load, a
    compile event inside the window)."""
    @jax.jit
    def total(params, state):
        return sum(jax.tree.leaves(params)) + sum(jax.tree.leaves(state))

    reg = registry(tmp_path)
    fn = reg.timed_jit("total", total)
    params = {f"l{i}": jnp.float32(i) for i in range(40)}
    held = []                               # alive: no identity comes back
    for n in range(40):
        state = {f"s{i}": jnp.float32(n) for i in range(20)}
        held.append(state)
        assert float(fn(params, state)) == sum(range(40)) + 20 * n
    c = reg.counters()["total"]
    assert (c["compiles"], c["dispatches"]) == (1, 40)
    assert len(fn._prog._runs) == 1 and len(fn._prog._tokens) == 2
    assert len(fn._prog._trees) <= 16


def test_setup_compile_cache_opens_the_store_where_the_cache_is_on(
        tmp_path, monkeypatch):
    """On exactly where the persistent cache is on, the backend is not the
    CPU and the process drives one device, in ``executables/`` of the
    cache's own directory."""
    from llama_fastapi_k8s_gpu_tpu.obs.devtime import DEVTIME
    from llama_fastapi_k8s_gpu_tpu.utils import jaxcache

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = DEVTIME.store
    try:
        assert not jax.config.jax_enable_compilation_cache   # conftest.py
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        monkeypatch.setattr(jax, "device_count", lambda: 1)
        jaxcache.setup_compile_cache()
        assert DEVTIME.store is None        # the tests keep out of both
        jax.config.update("jax_enable_compilation_cache", True)
        try:
            jaxcache.setup_compile_cache()
            assert DEVTIME.store.path == str(tmp_path / "executables")
            assert os.path.isdir(tmp_path / "executables")
            assert DEVTIME.store.floor_s == jaxcache.FLOOR_S == 0.5
            monkeypatch.setattr(jax, "device_count", lambda: 4)
            jaxcache.setup_compile_cache()
            assert DEVTIME.store is None    # no mesh has loaded one yet
            monkeypatch.setattr(jax, "device_count", lambda: 1)
            monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
            jaxcache.setup_compile_cache()
            assert DEVTIME.store is None    # XLA:CPU's AOT results: no
        finally:
            jax.config.update("jax_enable_compilation_cache", False)
    finally:
        DEVTIME.use_store(None if before is None else before.path)
