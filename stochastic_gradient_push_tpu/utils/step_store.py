"""A store of compiled train steps, found again without tracing them.

JAX's persistent compile cache keys an executable on the lowered module, so
a process that wants its train step from that cache must first trace and
lower the whole step from Python — flax apply, autodiff through every
layer, ``shard_map`` — only to find the executable it already had (PERF.md
§6, PR 38: 73–78 % of a warm step program's set-up).  This store keys the
step on what decides it, computed **before** any tracing, and keeps the
step's serialized executable under that key: a later process with the same
key loads it (``jax.experimental.serialize_executable``) and never traces.

The key is a sha256 over canonical bytes (never Python's ``hash()``), and
errs toward misses, since a wrong hit runs a stale program:

- the bytes of every ``.py`` file of the package;
- jax, jaxlib, flax, optax, numpy and Python versions, the platform's
  version string (libtpu's build), every device's platform, id and kind;
- every environment variable named ``XLA_*``, ``LIBTPU_*``, ``TPU_*``,
  ``JAX_*``, and a snapshot of ``jax.config``'s values;
- what the step is made of (:class:`_Walker`): the per-rank step's closure,
  walked down to code objects, closure cells, defaults, the module globals a
  package function reads, dataclass and NamedTuple fields, partials, numpy
  arrays by their bytes; the mesh and the in/out specs;
- the arguments' tree, shapes, dtypes, weak types and shardings.

Anything the walker cannot encode refuses: the step is a plain ``jax.jit``,
and the reason is told once on standard error.  The seed is an argument of
the init program and of the data, never of the step, so two seeds give one
key.

The store is off unless an entry point placed the compile cache
(``utils/compile_cache.py::place_compile_cache`` arms it, in the
subdirectory ``step_store`` of the cache's directory), and engages only
where ``jax.process_count() == 1``, on a TPU (:data:`PLATFORMS`).
Unarmed, :func:`jit` *is* ``jax.jit``.  JAX's own size cap
(``jax_compilation_cache_max_size``) evicts only the ``*-cache`` files at
the top of its directory, so it neither counts nor evicts the store; the
store keeps its :data:`KEEP` newest entries itself.  Delete the
subdirectory to clear it.  Entries are unpickled: the store trusts its
directory as JAX trusts its compile cache.

A hit writes the step's row into the set-up ledger itself
(``telemetry/setup_ledger.py``: no trace, no lowering, the key, read and
load as its backend interval, cache ``"stored"``), so the ledger's cut and
totals stay whole.
"""

from __future__ import annotations

import dataclasses
import enum
import functools
import hashlib
import os
import pickle
import sys
import tempfile
import time
import types
import zlib

import numpy as np

__all__ = ["STORE_SUBDIR", "KEEP", "PLATFORMS", "arm", "disarm", "jit",
           "StoredStep", "Refused"]

STORE_SUBDIR = "step_store"
KEEP = 8              # newest entries kept: the six cells' steps and room
_SUFFIX = ".step"
_FORMAT = 1           # of an entry's contents; part of the key
_ENV_PREFIXES = ("XLA_", "LIBTPU_", "TPU_", "JAX_")
_VERSIONED = ("jax", "jaxlib", "flax", "optax", "numpy")
_PACKAGE_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_PACKAGE = os.path.basename(_PACKAGE_DIR)
_STALE_TMP_S = 3600   # a temp file this old is a write that died
# where a serialized executable is self-contained: an XLA:CPU one may call
# kernels its process compiled for another program ("Function
# multiply_power_fusion not found" in the next process: my CPU runs, PR 39)
PLATFORMS = ("tpu",)

_dir: str | None = None
_logged: set[str] = set()     # lines already on standard error


def arm(cache_dir: str) -> None:
    """Keep steps under ``<cache_dir>/step_store`` from now on."""
    global _dir
    _dir = os.path.join(cache_dir, STORE_SUBDIR)


def disarm() -> None:
    """Back to plain ``jax.jit`` for steps built from now on."""
    global _dir
    _dir = None


def jit(fun, *, material, donate_argnums=()):
    """``jax.jit(fun, donate_argnums=...)``, kept in the store where it is
    armed.  ``material`` is what ``fun`` is made of — the per-rank step
    closure, the mesh, the specs, every flag of the wrapper — for the key;
    ``fun`` itself (a ``shard_map``) is JAX's and is not walked."""
    import jax

    jitted = jax.jit(fun, donate_argnums=donate_argnums)
    if _dir is None:
        return jitted
    return StoredStep(jitted, fun.__name__, (material, donate_argnums))


class Refused(Exception):
    """The key cannot be made: the step stays a plain ``jit``."""


@dataclasses.dataclass
class _Found:
    key: str
    path: str
    label: str                  # the step's name and the key's head, to log
    compiled: object | None = None     # the loaded step on a hit


class StoredStep:
    """A train step with the ``jit`` contract the loops and the benchmark
    use: ``lower(*args).compile()`` (then ``as_text()`` /
    ``memory_analysis()``) and calls.

    Hit: ``lower`` makes the key, finds an entry and loads it; ``compile``
    returns the loaded ``Compiled`` and calls dispatch it.  Miss: the real
    ``jit`` lowers and compiles as ever, and ``compile`` writes the
    executable to the store; calls go to the ``jit``.  Refused or off: the
    ``jit`` alone.  Arguments the loaded step was not built for (the loops
    build their step twice: ROADMAP S9) are looked up once more, then go to
    the ``jit`` for good."""

    def __init__(self, jitted, name: str, material):
        self._jit = jitted
        self.__name__ = name
        self._material = material
        self._static: bytes | None = None
        self._call = None
        self._lookups = 0

    def __call__(self, *args):
        if self._call is None:
            self._first(args)
        call = self._call
        if call is self._jit:
            return call(*args)
        try:
            return call(*args)
        except (TypeError, ValueError):
            # other shapes or shardings, refused before anything ran
            if self._lookups > 1:
                self._call = self._jit
            else:
                self._first(args)
            return self._call(*args)

    def lower(self, *args):
        found = self._find(args)
        if found is not None and found.compiled is not None:
            return _Loaded(found.compiled)
        lowered = self._jit.lower(*args)
        return lowered if found is None else _Writing(lowered, found)

    def key(self, *args) -> str:
        """The store's key for a call with ``args`` (raises
        :class:`Refused`)."""
        if self._static is None:
            self._static = _Walker().digest(self._material)
        h = hashlib.sha256()
        for part in (str(_FORMAT).encode(), self.__name__.encode(),
                     _source_digest(_PACKAGE_DIR), _runtime_digest(),
                     self._static, _args_digest(args)):
            h.update(len(part).to_bytes(8, "little") + part)
        return h.hexdigest()

    def _first(self, args) -> None:
        found = self._find(args)
        if found is not None and found.compiled is None:
            _Writing(self._jit.lower(*args), found).compile()

    def _find(self, args) -> _Found | None:
        """Decide where calls go, and find the entry; ``None`` where the
        store does not engage."""
        import jax

        from ..telemetry.setup_ledger import LEDGER

        self._call = self._jit
        self._lookups += 1
        if _dir is None:
            return None
        platform = jax.devices()[0].platform
        if platform not in PLATFORMS or jax.process_count() != 1:
            LEDGER.note_store(f"off ({platform}, "
                              f"{jax.process_count()} processes)")
            return None
        start = time.time()
        try:
            key = self.key(*args)
        except Refused as why:
            _note(f"refused: {why}", self.__name__)
            return None
        found = _Found(key, os.path.join(_dir, key + _SUFFIX),
                       f"{self.__name__} {key[:16]}")
        found.compiled = _load(found)
        if found.compiled is not None:
            self._call = found.compiled
            end = time.time()
            LEDGER.stored(self.__name__, start, end)
            _note("stored (hit)", found.label, f" in {end - start:.2f} s")
        return found


class _Loaded:
    """What ``lower`` gives on a hit: ``compile()`` is the loaded step."""

    def __init__(self, compiled):
        self._compiled = compiled

    def compile(self):
        return self._compiled


class _Writing:
    """The ``jit``'s own ``Lowered`` on a miss; ``compile()`` writes the
    executable to the store on its way out."""

    def __init__(self, lowered, found: _Found):
        self._lowered = lowered
        self._found = found

    def __getattr__(self, name):
        return getattr(self._lowered, name)

    def compile(self, *args, **kwargs):
        compiled = self._lowered.compile(*args, **kwargs)
        if not args and not kwargs:
            _write(self._found, compiled)
        return compiled


def _note(text: str, label: str, detail: str = "") -> None:
    """Tell the ledger (the ``set-up:`` line) and standard error, the
    latter once a line: which step, which key, what the store did."""
    from ..telemetry.setup_ledger import LEDGER

    LEDGER.note_store(text)
    line = f"step store: {label}: {text}{detail}"
    if line not in _logged:
        _logged.add(line)
        print(line, file=sys.stderr, flush=True)


# -- entries -----------------------------------------------------------------


def _load(found: _Found):
    """The loaded step of ``found``'s entry, or ``None``: absent,
    unreadable, corrupt or refused by the runtime are all a miss."""
    from jax.experimental.serialize_executable import deserialize_and_load

    path = found.path
    try:
        with open(path, "rb") as f:
            entry = pickle.loads(zlib.decompress(f.read()))
    except FileNotFoundError:
        return None
    except Exception as e:  # sgplint: disable=SGPL007 (damage is a miss)
        _note(f"entry unreadable ({type(e).__name__}): miss", found.label)
        return None
    try:
        if entry["format"] != _FORMAT or entry["key"] != found.key:
            raise ValueError("another key's entry")
        import jax

        devices = {d.id: d for d in jax.devices()}
        compiled = deserialize_and_load(
            entry["payload"], entry["in_tree"], entry["out_tree"],
            execution_devices=[devices[i] for i in entry["devices"]])
    except Exception as e:  # sgplint: disable=SGPL007 (refused: a miss)
        _note(f"entry not loaded ({type(e).__name__}: {e}): miss",
              found.label)
        return None
    try:
        os.utime(path)          # newest again: eviction keeps it
    except OSError:
        pass
    return compiled


def _write(found: _Found, compiled) -> None:
    """Serialize ``compiled`` under its key: a temp file renamed into
    place, then the oldest entries past :data:`KEEP` removed.  A failure
    leaves the store as it was and the run as it would be."""
    from jax.experimental.serialize_executable import serialize

    tmp, start = None, time.time()
    try:
        payload, in_tree, out_tree = serialize(compiled)
        devices = [d.id for d in
                   compiled._executable._unloaded_executable.device_list]
        blob = zlib.compress(pickle.dumps(
            {"format": _FORMAT, "key": found.key, "payload": payload,
             "in_tree": in_tree, "out_tree": out_tree, "devices": devices},
            protocol=pickle.HIGHEST_PROTOCOL), 1)
        directory = os.path.dirname(found.path)
        os.makedirs(directory, exist_ok=True)
        fd, tmp = tempfile.mkstemp(dir=directory, prefix=".tmp-")
        with os.fdopen(fd, "wb") as f:
            f.write(blob)
        os.replace(tmp, found.path)
        tmp = None
        _note("miss, written", found.label, f": {len(blob)} bytes in "
              f"{time.time() - start:.2f} s")
    except Exception as e:  # sgplint: disable=SGPL007 (never the run's)
        _note(f"miss, not written ({type(e).__name__}: {e})", found.label)
    finally:
        if tmp is not None:
            try:
                os.unlink(tmp)
            except OSError:
                pass
    _evict(os.path.dirname(found.path))


def _evict(directory: str) -> None:
    try:
        names = os.listdir(directory)
    except OSError:
        return
    now = time.time()
    entries = []
    for name in names:
        path = os.path.join(directory, name)
        try:
            mtime = os.stat(path).st_mtime
            if name.startswith(".tmp-") and now - mtime > _STALE_TMP_S:
                os.unlink(path)
            elif name.endswith(_SUFFIX):
                entries.append((mtime, path))
        except OSError:
            pass
    for _, path in sorted(entries, reverse=True)[KEEP:]:
        try:
            os.unlink(path)
        except OSError:
            pass


# -- the key ------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _source_digest(root: str) -> bytes:
    """sha256 over every ``.py`` file under ``root``: its path from there
    and its bytes, in sorted order."""
    h = hashlib.sha256()
    for base, dirs, files in os.walk(root):
        dirs.sort()
        for name in sorted(files):
            if name.endswith(".py"):
                path = os.path.join(base, name)
                with open(path, "rb") as f:
                    data = f.read()
                rel = os.path.relpath(path, root).encode()
                h.update(len(rel).to_bytes(8, "little") + rel)
                h.update(len(data).to_bytes(8, "little") + data)
    return h.digest()


def _runtime_digest() -> bytes:
    """Versions, devices, environment and ``jax.config``, read now."""
    import importlib.metadata

    import jax

    parts = [sys.version]
    for dist in _VERSIONED:
        try:
            parts.append(f"{dist}={importlib.metadata.version(dist)}")
        except importlib.metadata.PackageNotFoundError:
            parts.append(f"{dist}=none")
    devices = jax.devices()
    parts.append(devices[0].client.platform_version)
    parts += [f"{d.platform}:{d.id}:{d.device_kind}" for d in devices]
    parts += [f"{k}={v}" for k, v in sorted(os.environ.items())
              if k.startswith(_ENV_PREFIXES)]
    config = sorted(jax.config.values.items())
    return _Walker().digest((parts, config))


def _args_digest(args) -> bytes:
    """The call's tree and every leaf's shape, dtype, weak type and
    sharding: tree flattening only, nothing traced."""
    import jax

    leaves, tree = jax.tree_util.tree_flatten(args)
    walker = _Walker()
    described = [str(tree)]
    for leaf in leaves:
        if isinstance(leaf, jax.core.Tracer):
            raise Refused("called with traced arguments")
        aval = jax.typeof(leaf)
        described.append((
            tuple(aval.shape), str(aval.dtype), bool(aval.weak_type),
            walker.digest(getattr(leaf, "sharding", None)),
            getattr(leaf, "committed", None)))
    return walker.digest(described)


class _Walker:
    """Canonical digests of Python values: equal values give equal bytes
    in any process, and anything with no canonical form raises
    :class:`Refused`."""

    _SCALARS = (type(None), bool, int, str, bytes, type(Ellipsis),
                type(NotImplemented))

    def __init__(self):
        self._memo: dict[int, bytes] = {}
        self._open: set[int] = set()
        self._kept: list = []      # holds every object walked: ids stay unique

    def digest(self, obj) -> bytes:
        i = id(obj)
        if i in self._memo:
            return self._memo[i]
        if i in self._open:        # a cycle: the way back, by its kind
            return b"cycle:" + _qualname(type(obj)).encode()
        self._open.add(i)
        try:
            h = hashlib.sha256()
            for part in self._parts(obj):
                if isinstance(part, str):
                    part = part.encode()
                h.update(len(part).to_bytes(8, "little") + part)
            d = h.digest()
        finally:
            self._open.discard(i)
        self._memo[i] = d
        self._kept.append(obj)
        return d

    def _parts(self, obj):
        import jax

        kind = type(obj)
        yield _qualname(kind)
        if kind in self._SCALARS or isinstance(obj, enum.Enum):
            yield repr(obj)
        elif kind is float:
            yield obj.hex()
        elif kind is complex:
            yield obj.real.hex() + obj.imag.hex()
        elif isinstance(obj, type):
            if "<locals>" in obj.__qualname__:
                # made by a function call: its name does not say how
                raise Refused(f"a class made in a function, {obj.__name__}")
            yield _qualname(obj)
        elif isinstance(obj, types.ModuleType):
            yield obj.__name__
        elif isinstance(obj, np.ndarray):
            if obj.dtype.hasobject:
                raise Refused("a numpy array of objects")
            yield from (obj.dtype.str, repr(obj.shape),
                        np.ascontiguousarray(obj).tobytes())
        elif isinstance(obj, np.generic):
            yield from (obj.dtype.str, obj.tobytes())
        elif isinstance(obj, np.dtype):
            yield repr(obj)
        elif isinstance(obj, types.CodeType):
            yield from self._code(obj)
        elif isinstance(obj, types.FunctionType):
            yield from self._function(obj)
        elif isinstance(obj, types.MethodType):
            yield from (self.digest(obj.__func__),
                        self.digest(obj.__self__))
        elif isinstance(obj, types.BuiltinFunctionType):
            owner = obj.__self__
            yield from (getattr(obj, "__module__", None) or "",
                        obj.__qualname__)
            if owner is not None and not isinstance(owner, types.ModuleType):
                yield self.digest(owner)
        elif isinstance(obj, functools.partial):
            yield from (self.digest(obj.func), self.digest(obj.args),
                        self.digest(obj.keywords))
        elif isinstance(obj, tuple) and hasattr(obj, "_fields"):
            yield from self._namespace(zip(obj._fields, obj))
        elif isinstance(obj, slice):
            yield self.digest((obj.start, obj.stop, obj.step))
        elif isinstance(obj, (tuple, list)):
            yield from (self.digest(v) for v in obj)
        elif isinstance(obj, (set, frozenset)):
            yield from sorted(self.digest(v) for v in obj)
        elif isinstance(obj, dict):
            yield from sorted(self.digest(k) + self.digest(v)
                              for k, v in obj.items())
            if getattr(obj, "default_factory", None) is not None:
                yield self.digest(obj.default_factory)
        elif dataclasses.is_dataclass(obj):
            yield from self._namespace(
                (f.name, getattr(obj, f.name, None))
                for f in dataclasses.fields(obj))
        elif isinstance(obj, jax.sharding.Mesh):
            yield from (repr(obj.axis_names), repr(obj.devices.shape),
                        repr([d.id for d in obj.devices.flat]),
                        repr(getattr(obj, "axis_types", None)))
        elif isinstance(obj, jax.sharding.NamedSharding):
            yield from (self.digest(obj.mesh), repr(obj.spec),
                        repr(obj.memory_kind))
        elif isinstance(obj, jax.sharding.SingleDeviceSharding):
            yield from (repr(sorted(d.id for d in obj.device_set)),
                        repr(obj.memory_kind))
        elif isinstance(obj, jax.sharding.PartitionSpec):
            yield repr(obj)
        elif isinstance(obj, jax.Device):
            yield f"{obj.platform}:{obj.id}:{obj.device_kind}"
        elif _ours(kind):
            yield from self._namespace(_state(obj))
        else:
            raise Refused(f"cannot encode a {_qualname(kind)}")

    def _namespace(self, items):
        for k, v in sorted(items, key=lambda kv: kv[0]):
            yield k
            yield self.digest(v)

    def _code(self, code):
        yield from (code.co_code, code.co_exceptiontable,
                    repr((code.co_argcount, code.co_posonlyargcount,
                          code.co_kwonlyargcount, code.co_flags,
                          code.co_names, code.co_varnames,
                          code.co_freevars, code.co_cellvars)))
        yield self.digest(code.co_consts)

    def _function(self, fn):
        yield from (fn.__module__ or "", fn.__qualname__)
        closure = tuple(c.cell_contents if _filled(c) else _EMPTY_CELL
                        for c in fn.__closure__ or ())
        yield from (self.digest(closure), self.digest(fn.__defaults__),
                    self.digest(fn.__kwdefaults__))
        if not _ours(fn):
            return                   # third-party code: its version stands
        yield self.digest(fn.__code__)
        read = {}
        for name in _names(fn.__code__):
            if name in fn.__globals__:
                read[name] = fn.__globals__[name]
        yield from self._namespace(read.items())


_EMPTY_CELL = "<empty cell>"


def _filled(cell) -> bool:
    try:
        cell.cell_contents
    except ValueError:
        return False
    return True


def _names(code) -> set[str]:
    """Every global a code object and the code nested in it may read."""
    names = set(code.co_names)
    for const in code.co_consts:
        if isinstance(const, types.CodeType):
            names |= _names(const)
    return names


def _ours(obj) -> bool:
    module = getattr(obj, "__module__", None) or ""
    return module == _PACKAGE or module.startswith(_PACKAGE + ".")


def _state(obj):
    """An object of one of the package's classes, by its attributes."""
    items = dict(getattr(obj, "__dict__", {}))
    for cls in type(obj).__mro__:
        for slot in getattr(cls, "__slots__", ()):
            if slot not in ("__dict__", "__weakref__") and hasattr(obj, slot):
                items[slot] = getattr(obj, slot)
    return items.items()


def _qualname(kind) -> str:
    return f"{getattr(kind, '__module__', '')}.{kind.__qualname__}"
