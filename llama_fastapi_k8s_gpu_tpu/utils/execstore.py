"""The executable store: entry programs' compiled executables on disk, under
a key that needs no trace.

The persistent XLA cache (utils/jaxcache.py) is keyed by the program's HLO,
so a start that finds every executable there still runs the Python of every
program, lowers every ``pallas_call`` to Mosaic and hashes the module only
to LOOK its executable UP: 8-44 s of every cached start (PERF.md section 5,
PR 55).  This store keeps the same bytes under a key computed from what the
call site can see BEFORE a trace, and :class:`StoredJit` loads them instead
(``jax.experimental.serialize_executable``).  This module is the compile
layer's (it sits beside utils/jaxcache.py, which opens the store where the
persistent cache is on): it decides, for every call of an entry program,
whether the jit or a stored executable dispatches it.  ``obs/devtime.py
_TimedJit`` wraps that decision and only counts what it was (``how``):
whether the registry is armed changes no dispatch.

**The key is the whole correctness question**: a stale executable is a
wrong answer, not a slow one.  :func:`program_key` holds everything that
trace-time Python reads (docs/RUNBOOK.md "The executable store" has the
list for operators):

- the program's name, the jit's own parameters (static and donated
  arguments, ``keep_unused``, ``inline``, compiler options) and the
  ``key=`` a factory passes through ``timed_jit`` for what its closure
  holds.  A jit whose function closes over anything and passes no ``key=``
  is never stored;
- the static arguments BY VALUE (``cfg: ModelConfig``, ``n_steps``,
  ``top_k``, stop ids): dataclasses field by field, tuples, plain scalars.
  A static value of any other type makes the call unkeyable: it stays on the
  jit path;
- the dynamic arguments' tree structure and, leaf by leaf, dtype, shape,
  weak type and placement (:func:`placement`: which device holds which
  index, whatever the sharding is called);
- every ``LFKT_*`` environment variable but the ones that name where the
  process listens, where its files are and what the tracer samples
  (utils/provenance.py ``trace_env``), ``XLA_FLAGS``, ``LIBTPU_INIT_ARGS``, and
  the ``jax.config`` values a trace or a lowering reads;
- the probe verdicts so far (ops/pallas/probe.py ``verdicts``) and the
  degrade ledger: they decide which kernels a trace may use;
- a hash of the package's source files; the versions of ``jax`` and
  ``jaxlib`` and the backend's own version string (libtpu's build); device
  kind and count, process count.

One file a key, ``<source hash>-<program>-<key hash>.lfktx``: a compressed
pickle (zstandard where installed, else zlib: what JAX's own cache does) of
the key's text, the payload, the two tree definitions and the id of the
device the executable runs on.  A build that
took under the persistent cache's own floor writes nothing: the program
stays on the jit (a later start builds it there again, in the time the
jit's own first call takes).  Files are written to a temporary name and
renamed; a file that does not load is deleted and counted; a directory
that cannot be written turns the store off for the process with one log
line.  Files of another source hash are pruned when a new one is written.

**One device only** (utils/jaxcache.py opens the store where
``jax.device_count() == 1``): every measured start is a one-chip start
and the tree has no program that spans devices.  An executable loaded
onto the wrong device order would be a wrong answer, not a slow one: a
four-chip run that shows built, loaded and jit replies equal byte for
byte comes before that gate goes.

**The files are pickles**: reading one runs what it says.  The cache
volume must be writable by the serving user alone (docs/RUNBOOK.md).
"""

from __future__ import annotations

import dataclasses
import functools
import hashlib
import logging
import os
import pickle
import sys
import tempfile
import threading
import time
import types
import weakref
import zlib

from .jaxcache import FLOOR_S

logger = logging.getLogger(__name__)

#: the record's layout; part of the key's text, so a change misses
FORMAT = 1
SUFFIX = ".lfktx"
#: ``jax.config`` values that a trace or a lowering reads
_JAX_CONFIG = ("jax_enable_x64", "jax_default_matmul_precision",
               "jax_default_prng_impl", "jax_threefry_partitionable",
               "jax_numpy_dtype_promotion", "jax_numpy_rank_promotion",
               "jax_use_shardy_partitioner", "jax_disable_jit",
               "jax_debug_nans", "jax_debug_infs", "jax_enable_checks")
_ZSTD_MAGIC = b"\x28\xb5\x2f\xfd"


class Unkeyable(Exception):
    """This call has no key: it holds something whose value the store
    cannot write down (the call stays on the jit path)."""


def _compress(raw: bytes) -> bytes:
    try:
        import zstandard
    except ImportError:
        return zlib.compress(raw)
    return zstandard.ZstdCompressor().compress(raw)


def _decompress(blob: bytes) -> bytes:
    if blob[:4] == _ZSTD_MAGIC:
        import zstandard

        return zstandard.ZstdDecompressor().decompress(blob)
    return zlib.decompress(blob)


# ---------------------------------------------------------------------------
# the key
# ---------------------------------------------------------------------------

def encode_static(v) -> str:
    """A static value, written so that equal text means an equal trace and
    the text is the same in every process (no ``hash()``, no address)."""
    if v is None or isinstance(v, (bool, int, float, str, bytes)):
        return repr(v)
    if isinstance(v, (tuple, list)):
        return "(" + ",".join(encode_static(x) for x in v) + ")"
    if dataclasses.is_dataclass(v) and not isinstance(v, type):
        return type(v).__qualname__ + "(" + ",".join(
            f"{f.name}={encode_static(getattr(v, f.name))}"
            for f in dataclasses.fields(v)) + ")"
    raise Unkeyable(f"a static {type(v).__name__}")


@functools.lru_cache(maxsize=4096)
def placement(sharding, shape: tuple) -> tuple:
    """Where an array of ``shape`` under ``sharding`` lies: (memory kind,
    ((device id, the index it holds), ...)), by public API alone.  ONE
    value for every name of one placement: on one device ``P()``,
    ``P(None, None)``, ``P('dp', None)`` and a ``SingleDeviceSharding`` are
    the same executable to JAX (a ``Compiled`` takes any of them, the jit
    compiles nothing for the second), and an output takes the name of
    whichever input it matches, so a state that passes through two
    programs comes back under several.  Keyed by name, each would be a
    signature of its own: a file, a load, a compile event in the window."""
    return (sharding.memory_kind, tuple(sorted(
        (d.id, index)
        for d, index in sharding.devices_indices_map(shape).items())))


def describe_dynamic(leaf) -> str:
    """One dynamic leaf as the compiler sees it: never its values.  Not
    whether the array is committed: of two arrays in one placement the
    committed and the uncommitted one lower to the same program, and a
    ``Compiled`` refuses what it was not built for itself."""
    if isinstance(leaf, (bool, int, float, complex)):
        return f"py:{type(leaf).__name__}"        # weakly typed, by type
    if not (hasattr(leaf, "shape") and hasattr(leaf, "dtype")):
        raise Unkeyable(f"a dynamic {type(leaf).__name__}")
    out = f"{leaf.dtype}[{','.join(str(d) for d in leaf.shape)}]"
    if getattr(leaf, "weak_type", False):
        out += "w"
    sharding = getattr(leaf, "sharding", None)
    if sharding is not None:
        out += f" {placement(sharding, tuple(leaf.shape))}"
    return out


@functools.cache
def source_hash() -> str:
    """sha256 over the package's ``.py`` files (names and bytes), 16 hex
    digits: 5 ms, once a process."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    h = hashlib.sha256()
    for d, dirs, files in os.walk(root):
        dirs.sort()
        for f in sorted(files):
            if f.endswith(".py"):
                path = os.path.join(d, f)
                h.update(os.path.relpath(path, root).encode())
                with open(path, "rb") as src:
                    h.update(src.read())
    return h.hexdigest()[:16]


def process_facts(degrades: list[dict]) -> list[str]:
    """The lines of a key that are the same for every program of this
    process at this moment: read anew at every first call of a signature
    (a test, or an operator's shell, may change the environment between
    two engines of one process)."""
    import jax
    import jaxlib

    from ..utils.provenance import trace_env

    lines = [f"env {k}={v}" for k, v in trace_env()]
    lines += [f"env {k}={os.environ.get(k, '')}"
              for k in ("XLA_FLAGS", "LIBTPU_INIT_ARGS")]
    lines += [f"config {k}={getattr(jax.config, k, None)}"
              for k in _JAX_CONFIG]
    probe = sys.modules.get(__package__.rsplit(".", 1)[0]
                            + ".ops.pallas.probe")
    if probe is not None:        # not imported: no probe ran, no verdict
        lines += [f"probe {k}={v}" for k, v in sorted(
            probe.verdicts().items())]
    lines += sorted(f"degrade {d['program']}: {d['reason']}"
                    for d in degrades)
    devs = jax.devices()
    lines.append(f"source {source_hash()}")
    lines.append(f"versions jax={jax.__version__} jaxlib={jaxlib.__version__}"
                 f" backend={devs[0].client.platform_version}")
    lines.append(f"devices {devs[0].platform} "
                 f"{sorted({d.device_kind for d in devs})} x{len(devs)} "
                 f"processes={jax.process_count()}")
    return lines


def program_key(name: str, info, extra, args: tuple, kwargs: dict,
                static_nums, static_names, degrades: list[dict]) -> str:
    """The key's text for one call of program ``name`` (``info``: the jit's
    ``PjitInfo``; ``extra``: the factory's ``key=``).  Raises
    :class:`Unkeyable`."""
    import jax

    lines = [f"format {FORMAT}", f"program {name}",
             f"extra {encode_static(extra)}",
             "jit " + " ".join(
                 f"{k}={getattr(info, k, None)!r}" for k in (
                     "static_argnums", "static_argnames", "donate_argnums",
                     "donate_argnames", "keep_unused", "inline",
                     "compiler_options_kvs", "in_shardings_leaves",
                     "out_shardings_leaves", "in_layouts_leaves",
                     "out_layouts_leaves", "device", "backend"))]
    if " at 0x" in lines[-1]:
        raise Unkeyable("a jit parameter without a stable text")
    dyn_args = []
    for i, a in enumerate(args):
        if i in static_nums:
            lines.append(f"static {i}={encode_static(a)}")
        else:
            dyn_args.append(a)
    dyn_kwargs = {}
    for k in sorted(kwargs):
        if k in static_names:
            lines.append(f"static {k}={encode_static(kwargs[k])}")
        else:
            dyn_kwargs[k] = kwargs[k]
    leaves, tree = jax.tree_util.tree_flatten((dyn_args, dyn_kwargs))
    tree = str(tree)
    if " at 0x" in tree:
        raise Unkeyable("a pytree node without a stable text")
    lines.append(f"tree {tree}")
    lines += [f"leaf {i} {describe_dynamic(leaf)}"
              for i, leaf in enumerate(leaves)]
    return "\n".join(lines + process_facts(degrades))


# ---------------------------------------------------------------------------
# the files
# ---------------------------------------------------------------------------

class ExecStore:
    """One directory of executables.  Every method that touches the disk
    answers a failure by turning the store off (``off``: the reason), never
    by raising: a start without the store is today's start."""

    # the counters move under one mutex (lfkt-lint LOCK001); ``off`` is a
    # plain attribute read on the first call of a signature
    _GUARDED_BY = {"load_failures": "_lock", "files_written": "_lock",
                   "bytes_written": "_lock", "pruned": "_lock"}
    _SHARED_ATOMIC = ("off",)

    def __init__(self, path: str, floor_s: float = FLOOR_S):
        self.path = path
        #: a build under this many seconds is not worth a file: the
        #: persistent cache's own floor (utils/jaxcache.py)
        self.floor_s = floor_s
        self._lock = threading.Lock()
        self.off: str | None = None
        self.load_failures = 0
        self.files_written = 0
        self.bytes_written = 0
        self.pruned = 0
        try:
            os.makedirs(path, exist_ok=True)
            if not os.access(path, os.W_OK | os.X_OK):
                raise PermissionError(f"{path} is not writable")
        except OSError as e:
            self._turn_off(e)

    def _turn_off(self, why) -> None:
        if self.off is None:
            self.off = f"{type(why).__name__}: {why}"[:300]
            logger.warning(
                "executable store off for this process (%s): every program "
                "is built through the jit path, as without the store",
                self.off, extra={"dir": self.path})

    def _file(self, name: str, key: str) -> str:
        digest = hashlib.sha256(key.encode()).hexdigest()[:32]
        safe = "".join(c if c.isalnum() or c in "_.-" else "_" for c in name)
        return os.path.join(self.path,
                            f"{source_hash()}-{safe}-{digest}{SUFFIX}")

    def failed(self, name: str, key: str, why) -> None:
        """A file that did not load: counted, deleted."""
        with self._lock:
            self.load_failures += 1
        path = self._file(name, key)
        logger.warning("executable store: %s did not load (%s: %s); deleted, "
                       "the program is built again", os.path.basename(path),
                       type(why).__name__, str(why)[:200])
        try:
            os.remove(path)
        except OSError:
            pass

    def read(self, name: str, key: str) -> dict | None:
        """The record under ``key``, or None: no file, or one that cannot
        be read back (counted, deleted)."""
        if self.off is not None:
            return None
        path = self._file(name, key)
        try:
            with open(path, "rb") as f:
                blob = f.read()
        except FileNotFoundError:
            return None
        except OSError as e:
            self._turn_off(e)
            return None
        try:
            rec = pickle.loads(_decompress(blob))
            if rec["format"] != FORMAT or rec["key"] != key:
                raise ValueError("another key's or another layout's record")
        except Exception as e:  # noqa: BLE001 -- truncated, another pickle, another zstd: any of them means "build it"
            self.failed(name, key, e)
            return None
        return rec

    def write(self, name: str, key: str, **fields) -> None:
        """Write the record atomically."""
        if self.off is not None:
            return
        path = self._file(name, key)
        blob = _compress(pickle.dumps(
            {"format": FORMAT, "key": key, **fields},
            protocol=pickle.HIGHEST_PROTOCOL))
        try:
            fd, tmp = tempfile.mkstemp(dir=self.path, suffix=".part")
            try:
                with os.fdopen(fd, "wb") as f:
                    f.write(blob)
                os.replace(tmp, path)
            except BaseException:
                os.remove(tmp)
                raise
        except OSError as e:
            self._turn_off(e)
            return
        pruned = self._prune()
        with self._lock:
            self.files_written += 1
            self.bytes_written += len(blob)
            self.pruned += pruned

    def _prune(self) -> int:
        """Delete the files of every other source hash (a tree that is no
        longer the one running): the directory holds one version's
        executables, not every version's the volume ever saw."""
        mine = source_hash() + "-"
        n = 0
        try:
            for fn in os.listdir(self.path):
                if fn.endswith(SUFFIX) and not fn.startswith(mine):
                    os.remove(os.path.join(self.path, fn))
                    n += 1
        except OSError:
            pass
        return n

    def stats(self) -> dict:
        """The store's own part of ``/debug/compiles``
        ``executable_store``: where it is, whether it is on, what it holds
        now."""
        files = size = 0
        try:
            with os.scandir(self.path) as it:
                for e in it:
                    if e.name.endswith(SUFFIX):
                        files += 1
                        size += e.stat().st_size
        except OSError:
            pass
        with self._lock:
            return {"dir": self.path, "on": self.off is None,
                    "off_reason": self.off, "floor_s": self.floor_s,
                    "files": files, "bytes": size,
                    "files_written": self.files_written,
                    "bytes_written": self.bytes_written,
                    "pruned": self.pruned,
                    "load_failures": self.load_failures}


# ---------------------------------------------------------------------------
# the programs
# ---------------------------------------------------------------------------

#: what dispatches a signature that stays on the jit itself
JIT = object()
#: how a first-seen signature's executable came to be: loaded from the
#: store; built and written there; built here and not kept (under the
#: store's floor, or not serializable: the jit dispatches it and finds the
#: build in its own caches)
LOADED, BUILT, NOT_KEPT = "loaded", "built", "not_kept"
_PY_SCALARS = (bool, int, float, complex)
#: a dynamic argument tree of more leaves than this (the weights) has its
#: part of the per-call key held once per tree identity
_MEMO_LEAVES = 16


def _leaf_atom(leaf):
    """What the per-call key holds of one dynamic leaf: everything the
    store's key says of it (:func:`describe_dynamic`), as hashable objects
    and without a string."""
    try:      # a jax.Array
        shape = leaf.shape
        return (shape, leaf.dtype, leaf.weak_type,
                placement(leaf.sharding, shape))
    except AttributeError:
        pass
    if isinstance(leaf, _PY_SCALARS):
        return type(leaf)
    return (leaf.shape, leaf.dtype)      # NumPy; anything else raises


def _load(rec: dict):
    """The ``Compiled`` of a store record, on the device it was built for
    (JAX's default is every device of the backend)."""
    import jax
    from jax.experimental.serialize_executable import deserialize_and_load

    by_id = {d.id: d for d in jax.devices()}
    return deserialize_and_load(
        rec["payload"], rec["in_tree"], rec["out_tree"],
        execution_devices=[by_id[i] for i in rec["devices"]])


class StoredJit:
    """One jit function's signatures, and what dispatches each: the jit
    itself (:data:`JIT`), or a ``Compiled`` that was loaded from the store
    or built for it.  Chosen by what the program's first call observes: a
    first build under the store's floor leaves the whole program on the jit
    for good (``lane_write``, ``first_sample``, the ``kvpool_*`` copies: a
    lookup per call would cost them more than a start saves); else every
    call looks its signature up (:meth:`find`): static arguments by value,
    dynamic ones by aval and placement, a tree of many leaves (the weights)
    by identity.  A ``Compiled`` takes the dynamic arguments only
    (:meth:`dynamic`); donation holds as the jit's does."""

    __slots__ = ("name", "fn", "on_jit", "_info", "_extra", "_static_nums",
                 "_static_names", "_runs", "_trees", "_tokens", "_lock")

    # the signature table and the tree memo are filled under the program's
    # own mutex and read without it (a dict read is atomic)
    _GUARDED_BY = {"_runs": "_lock", "on_jit": "_lock",
                   "_trees": "_lock", "_tokens": "_lock"}

    def __init__(self, name: str, fn, key=None):
        self.name = name
        self.fn = fn
        # what the jit's decorator was given.  Anything but a plain
        # function that closes over nothing (a closure, a partial, a bound
        # method) has trace-time inputs no call shows: without a ``key``
        # for them it is never stored
        info = getattr(fn, "_jit_info", None)
        fun = getattr(fn, "_fun", None)
        if key is None and not (isinstance(fun, types.FunctionType)
                                and not fun.__closure__):
            info = None
        self._info = info
        self._extra = key
        self._static_nums = frozenset(getattr(info, "static_argnums", ()))
        self._static_names = frozenset(getattr(info, "static_argnames", ()))
        #: True: the jit dispatches every signature, none is looked up
        self.on_jit = info is None
        self._runs: dict = {}
        self._trees: dict = {}
        self._tokens: dict = {}
        self._lock = threading.Lock()

    def dynamic(self, args: tuple) -> tuple:
        nums = self._static_nums
        return tuple(a for i, a in enumerate(args) if i not in nums) \
            if nums else args

    def dynamic_kw(self, kwargs: dict) -> dict:
        names = self._static_names
        return {k: v for k, v in kwargs.items() if k not in names} \
            if kwargs and names else kwargs

    def _atom(self, a):
        """One dynamic argument's part of the per-call key."""
        if a is None:
            return None
        if not isinstance(a, (dict, list, tuple)):
            return _leaf_atom(a)
        memo = self._trees.get(id(a))
        if memo is not None and memo[0]() is not None:
            return memo[1]
        import jax

        leaves, tree = jax.tree_util.tree_flatten(a)
        atom = (tree, tuple(map(_leaf_atom, leaves)))
        if len(leaves) > _MEMO_LEAVES:
            # the weights: the same object at every call, hundreds of
            # leaves.  Its part of the key is a token for the atom (a
            # tuple's hash is computed anew at every lookup), held by
            # ``id`` for as long as the tree's first leaf lives (a weak
            # reference: the memo must not keep a model's weights on the
            # device after its engine is gone).  An ``id`` taken again by
            # another tree of other avals cannot give a wrong answer: a
            # ``Compiled`` checks its arguments' structure, avals and
            # shardings itself and raises
            try:
                alive = weakref.ref(leaves[0])
            except TypeError:
                return atom
            with self._lock:
                # a token is never given twice (the table of tokens only
                # grows, by one entry a distinct tree of avals); the memo
                # of identities is what gets emptied, so that a tree made
                # anew at every call (a state of many leaves) cannot fill it
                atom = ("tree", self._tokens.setdefault(atom,
                                                        len(self._tokens)))
                if len(self._trees) >= 16:
                    self._trees.clear()
                self._trees[id(a)] = (alive, atom)
        return atom

    def _call_key(self, args: tuple, kwargs: dict) -> tuple:
        """The signature of this call, for the program's own table: static
        arguments by value (hashable, as ``jax.jit`` demands), dynamic
        ones by :meth:`_atom`."""
        nums, atom = self._static_nums, self._atom
        key = [a if i in nums else atom(a) for i, a in enumerate(args)]
        if kwargs:
            names = self._static_names
            key += [(k, kwargs[k] if k in names else atom(kwargs[k]))
                    for k in sorted(kwargs)]
        return tuple(key)

    def find(self, args: tuple, kwargs: dict):
        """What dispatches this call: a ``Compiled``, :data:`JIT`, or None
        for a signature not seen yet (:meth:`first` decides it)."""
        try:
            return self._runs.get(self._call_key(args, kwargs))
        except (AttributeError, TypeError):    # an argument the key cannot
            return JIT                         # hold: the jit's say

    def first(self, store: ExecStore, args: tuple, kwargs: dict,
              degrades: list[dict]):  # lfkt: blocks-under[_lock] -- a first call loads or builds under the program's own mutex by design: two threads that meet on an unseen signature must not build it twice, and a build is what the jit's own first call blocks on today
        """Decide an unseen signature: (what dispatches it from now on, how
        it came to be).  ``how`` is None where nothing was loaded or built
        HERE: another thread was first, or the call has no key (the jit's
        own first call follows and says what it did)."""
        key = self._call_key(args, kwargs)
        with self._lock:
            run = self._runs.get(key)
            if run is not None:
                return run, None
            run, how = self._load_or_build(store, args, kwargs, degrades)
            if not self._runs:          # the program's first signature
                self.on_jit = run is JIT
            self._runs[key] = run
        return run, how

    def _load_or_build(self, store: ExecStore, args: tuple, kwargs: dict,
                       degrades: list[dict]):  # lfkt: holds[_lock]
        """Never raises for the store's sake: whatever fails there, the
        program is built."""
        if store.off is not None:
            return JIT, None
        name = self.name
        try:
            text = program_key(name, self._info, self._extra, args, kwargs,
                               self._static_nums, self._static_names,
                               degrades)
        except Unkeyable as e:
            logger.info("program %s is not stored: %s", name, e)
            return JIT, None
        t0 = time.perf_counter()
        rec = store.read(name, text)
        if rec is not None:
            try:
                t1 = time.perf_counter()
                run = _load(rec)
                logger.info("program %s: executable loaded in %.2fs (the "
                            "file %.2fs, the load %.2fs; built in %.1fs)",
                            name, time.perf_counter() - t0, t1 - t0,
                            time.perf_counter() - t1, rec["build_s"])
                return run, LOADED
            except Exception as e:  # noqa: BLE001 -- another runtime, a changed pickle: any failure means "build it"
                store.failed(name, text, e)
        t0 = time.perf_counter()
        compiled = self.fn.lower(*args, **kwargs).compile()
        build_s = time.perf_counter() - t0
        if build_s < store.floor_s:
            return JIT, NOT_KEPT
        try:
            from jax.experimental.serialize_executable import serialize

            payload, in_tree, out_tree = serialize(compiled)
        except Exception as e:  # noqa: BLE001 -- a host callback, a tree that does not pickle: the program serves from the jit
            logger.info("program %s cannot be stored (%s: %s)", name,
                        type(e).__name__, str(e)[:200])
            return JIT, NOT_KEPT
        devices = [d.id for d in compiled.runtime_executable().local_devices()]
        store.write(name, text, payload=payload, in_tree=in_tree,
                    out_tree=out_tree, devices=devices, build_s=build_s)
        return compiled, BUILT
