"""AOT program library for zero-warmup serving.

Serializes compiled ``route_window_planes`` executables with
``jax.export`` and reloads them in a fresh process, keyed on the exact
``_note_dispatch_variant`` signatures the router already canonicalizes
dispatches to.  A warm process then serves its first window without
tracing or lowering the window program — ``route.dispatch.compiles``
stays 0.

Two constraints shape the design:

* ``jax.export`` BAKES static argnames into the exported program: the
  export call receives the full argument list (statics included, so
  tracing sees them), but ``Exported.call()`` must receive ONLY the
  remaining array arguments — passing a static raises a pytree
  structure mismatch.  ``_split_dynamic`` filters statics by name
  against the wrapped function's signature.
* The window program donates its state buffers, so argument avatars
  (``jax.ShapeDtypeStruct`` per array leaf, same trick as
  obs/devprof.py) are captured at note time, BEFORE the jit call
  consumes the args; export itself is deferred to ``save()`` so the
  serve path never pays a trace mid-route.

Provenance (jax/jaxlib versions, backend, git rev) is stamped into the
index; any mismatch refuses the whole library with a recorded reason
and falls back to the jit path — a stale library degrades to exactly
the pre-library behaviour, never to a wrong answer.

Stdlib + jax only; this module must not import route/ (the router
imports it lazily).
"""

from __future__ import annotations

import hashlib
import inspect
import json
import os
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import numpy as np

from ..obs.metrics import get_metrics

INDEX_NAME = "library.json"
LIBRARY_SCHEMA = 1


def _tupled(x):
    """Canonicalize a variant key: JSON round-trips tuples as lists,
    and live keys may carry numpy scalars — normalize both so the
    on-disk and in-process forms hash/repr identically."""
    if isinstance(x, (list, tuple)):
        return tuple(_tupled(v) for v in x)
    if isinstance(x, bool):
        return x
    if isinstance(x, np.integer):
        return int(x)
    if isinstance(x, np.floating):
        return float(x)
    return x


def key_id(key: Tuple) -> str:
    """Stable filename stem for a variant key."""
    return hashlib.sha256(repr(_tupled(key)).encode()).hexdigest()[:16]


def _is_array(a) -> bool:
    return isinstance(a, jax.Array)


def _avatarize(tree):
    """Replace array leaves with ShapeDtypeStructs (devprof idiom);
    python scalars/None pass through untouched."""
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)
        if _is_array(a) else a,
        tree, is_leaf=lambda a: _is_array(a) or a is None)


def _statics() -> set:
    """The window program's static argnames (route/ is imported
    lazily: the router imports this module)."""
    from ..route.planes import WINDOW_STATIC_ARGNAMES
    return set(WINDOW_STATIC_ARGNAMES)


def _positional_names(fn) -> List[str]:
    inner = getattr(fn, "__wrapped__", None) or getattr(fn, "_fun", fn)
    sig = inspect.signature(inner)
    return [p.name for p in sig.parameters.values()
            if p.kind in (p.POSITIONAL_ONLY, p.POSITIONAL_OR_KEYWORD)]


def _split_dynamic(fn, args: tuple, kwargs: dict):
    """Drop static-argname entries from (args, kwargs): the exported
    program has them baked in and its call() rejects them."""
    statics = _statics()
    names = _positional_names(fn)
    dyn_args = tuple(a for name, a in zip(names, args)
                     if name not in statics)
    if len(args) > len(names):  # defensive: extra positionals kept
        dyn_args = dyn_args + tuple(args[len(names):])
    dyn_kwargs = {k: v for k, v in kwargs.items() if k not in statics}
    return dyn_args, dyn_kwargs


def _sig_digest(fn, args: tuple, kwargs: dict) -> str:
    """Digest of the DYNAMIC call structure (treedef + leaf
    shapes/dtypes) plus the static values: detects a library entry
    whose baked program no longer matches the live call."""
    statics = _statics()
    names = _positional_names(fn)
    stat_repr = [(n, repr(a)) for n, a in zip(names, args)
                 if n in statics]
    stat_repr += sorted((k, repr(v)) for k, v in kwargs.items()
                        if k in statics)
    dyn_args, dyn_kwargs = _split_dynamic(fn, args, kwargs)
    leaves, treedef = jax.tree_util.tree_flatten((dyn_args, dyn_kwargs))
    parts = [str(treedef)]
    for leaf in leaves:
        if hasattr(leaf, "shape") and hasattr(leaf, "dtype"):
            parts.append(f"{tuple(leaf.shape)}:{leaf.dtype}")
        else:
            parts.append(repr(leaf))
    parts.append(repr(stat_repr))
    return hashlib.sha256("|".join(parts).encode()).hexdigest()[:16]


# pytree node types already registered for jax.export serialization.
# The window program's signature carries flax struct.dataclass pytrees
# (PlanesGraph, DeviceRRGraph, ...) whose treedefs land in the exported
# calling convention; jax.export refuses to (de)serialize unregistered
# node types, so both save() and dispatch() register every custom type
# found in the live call tree first.  Auxdata (the static fields of
# those dataclasses: shapes, spans, cell counts) round-trips through
# pickle — the library is a local, self-produced artifact, same trust
# domain as the persistent compile cache.
_SERIALIZABLE: set = set()
_NATIVE_NODES = (tuple, list, dict, type(None))


def _register_tree_serialization(tree) -> None:
    import pickle

    from jax import export as jexport

    def walk(td):
        nd = td.node_data()
        if nd is not None:
            t = nd[0]
            if t not in _SERIALIZABLE and t not in _NATIVE_NODES \
                    and not issubclass(t, _NATIVE_NODES):
                try:
                    jexport.register_pytree_node_serialization(
                        t,
                        serialized_name=(f"{t.__module__}."
                                         f"{t.__qualname__}"),
                        serialize_auxdata=pickle.dumps,
                        deserialize_auxdata=pickle.loads)
                except ValueError:
                    pass  # registered elsewhere (e.g. another library)
                _SERIALIZABLE.add(t)
        for c in td.children():
            walk(c)

    walk(jax.tree_util.tree_structure(tree))
    # the program's result, a NamedTuple, is in the exported calling
    # convention too
    from ..route.planes import WindowOut
    if WindowOut not in _SERIALIZABLE:
        try:
            jexport.register_namedtuple_serialization(
                WindowOut, serialized_name=(
                    f"{WindowOut.__module__}.{WindowOut.__qualname__}"))
        except ValueError:
            pass  # registered elsewhere
        _SERIALIZABLE.add(WindowOut)


def _provenance(repo_dir: Optional[str] = None) -> Dict[str, Any]:
    import jaxlib
    try:
        from ..obs.runstore import git_rev
        rev = git_rev(repo_dir)
    except Exception:  # graftlint: ignore[bare-except-swallow]
        # a checkout without git is an expected environment, not a
        # degrade event; the recorded outcome IS rev=None in the stamp
        rev = None
    return {
        "schema": LIBRARY_SCHEMA,
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "backend": jax.default_backend(),
        "git_rev": rev,
    }


class ProgramLibrary:
    """Directory of serialized route_window_planes executables.

    Lifecycle: a warm-up process routes once with the library attached
    (``note`` records each variant's avatarized args), then calls
    ``save()`` to export+serialize every noted variant.  A serving
    process constructs the library on the same directory, ``load()``s
    the index, and ``dispatch()`` serves matching variants from the
    deserialized executables — falling back to the jit path (and
    noting the variant for a later save) on any miss or error.
    """

    def __init__(self, directory: str, repo_dir: Optional[str] = None,
                 check_git_rev: bool = False):
        self.dir = os.path.abspath(directory)
        self.repo_dir = repo_dir
        # git rev changes on every commit while the window program
        # rarely does; the jax/jaxlib/backend triple is the binary
        # compatibility boundary, so rev checking is opt-in.
        self.check_git_rev = check_git_rev
        self.stale_reason: Optional[str] = None
        self._index: Dict[str, Dict[str, Any]] = {}   # kid -> meta
        self._keys: Dict[str, Tuple] = {}             # kid -> key
        self._loaded: Dict[str, Any] = {}             # kid -> Exported
        self._pending: Dict[str, Dict[str, Any]] = {} # kid -> capture
        self._dead: set = set()                       # kid evicted
        self.dropped: List[Tuple[str, str]] = []      # (kid, reason)
        self.fault_plan = None  # optional resil FaultPlan (set by serve)

    # ---------------------------------------------------------- load

    def load(self) -> int:
        """Read the index; returns the number of usable entries (0 and
        a ``stale_reason`` when provenance refuses the library)."""
        path = os.path.join(self.dir, INDEX_NAME)
        if not os.path.exists(path):
            self.stale_reason = "no_index"
            return 0
        try:
            with open(path) as f:
                idx = json.load(f)
        except (OSError, ValueError) as e:
            self.stale_reason = f"unreadable_index: {e}"
            return 0
        prov = idx.get("provenance", {})
        want = _provenance(self.repo_dir)
        checked = ["schema", "jax", "jaxlib", "backend"]
        if self.check_git_rev:
            checked.append("git_rev")
        for field in checked:
            if prov.get(field) != want[field]:
                self.stale_reason = (
                    f"provenance_mismatch:{field}"
                    f"({prov.get(field)}!={want[field]})")
                return 0
        self.stale_reason = None
        for kid, meta in idx.get("entries", {}).items():
            blob = os.path.join(self.dir, meta.get("file", ""))
            if not os.path.exists(blob):
                continue
            # Content checksum: a truncated/torn blob (crash mid-write
            # on a pre-atomic writer, disk corruption) must degrade to
            # the jit path here, not raise at first dispatch — and
            # must not pre-register its key as a warm variant.
            want_sha = meta.get("sha256")
            if want_sha is not None:
                with open(blob, "rb") as f:
                    got = hashlib.sha256(f.read()).hexdigest()
                if got != want_sha:
                    get_metrics().counter("route.serve.aot_errors").inc()
                    self.dropped.append(
                        (kid, f"checksum mismatch (torn file?): "
                              f"{got[:12]} != {want_sha[:12]}"))
                    self._dead.add(kid)
                    continue
            self._index[kid] = meta
            self._keys[kid] = _tupled(meta["key"])
        return len(self._index)

    def keys(self) -> List[Tuple]:
        """Variant keys available for zero-compile dispatch."""
        return list(self._keys.values())

    def _exported(self, kid: str):
        """Lazy-deserialize an entry (once per process)."""
        if kid in self._loaded:
            return self._loaded[kid]
        from jax import export as jexport
        meta = self._index[kid]
        with open(os.path.join(self.dir, meta["file"]), "rb") as f:
            blob = f.read()
        # re-verify at read time (load() may be long past): any
        # corruption raises here and dispatch()'s except degrades to
        # the jit path with an aot_errors count
        want_sha = meta.get("sha256")
        if want_sha is not None:
            got = hashlib.sha256(blob).hexdigest()
            if got != want_sha:
                raise ValueError(
                    f"library blob {meta['file']} checksum mismatch "
                    f"(torn file?)")
        exp = jexport.deserialize(bytearray(blob))
        self._loaded[kid] = exp
        return exp

    # ------------------------------------------------------- capture

    def note(self, key: Tuple, fn: Callable,
             args: tuple, kwargs: dict) -> None:
        """Record a variant's avatarized args for a later save().
        MUST run before the jit call donates the buffers."""
        kid = key_id(key)
        if kid in self._index or kid in self._pending or kid in self._dead:
            return
        self._pending[kid] = {
            "key": _tupled(key),
            "fn": fn,
            "av_args": _avatarize(args),
            "av_kwargs": _avatarize(kwargs),
            "sig": _sig_digest(fn, args, kwargs),
        }

    def save(self) -> int:
        """Export+serialize every pending variant; merge the index.
        Pays one trace+lower+compile per new variant — call at the end
        of a warm-up route, never mid-serve.  Returns entries written.
        """
        if not self._pending:
            return 0
        from jax import export as jexport
        os.makedirs(self.dir, exist_ok=True)
        written = 0
        for kid, cap in list(self._pending.items()):
            try:
                _register_tree_serialization(
                    (cap["av_args"], cap["av_kwargs"]))
                exp = jexport.export(cap["fn"])(
                    *cap["av_args"], **cap["av_kwargs"])
                blob = exp.serialize()
            except Exception as e:  # unexportable variant: skip, keep serving
                get_metrics().counter("route.serve.aot_errors").inc()
                self._dead.add(kid)
                del self._pending[kid]
                self.stale_reason = f"export_failed: {e}"
                continue
            fname = f"{kid}.jexp"
            # atomic blob install (tmp + rename) so a crash mid-export
            # can never leave a torn .jexp behind a valid index entry
            fpath = os.path.join(self.dir, fname)
            with open(fpath + ".tmp", "wb") as f:
                f.write(bytes(blob))
                f.flush()
                os.fsync(f.fileno())
            os.replace(fpath + ".tmp", fpath)
            self._index[kid] = {
                "key": list(cap["key"]),
                "file": fname,
                "sig": cap["sig"],
                "bytes": len(blob),
                "sha256": hashlib.sha256(bytes(blob)).hexdigest(),
            }
            self._keys[kid] = cap["key"]
            del self._pending[kid]
            written += 1
        index = {
            "provenance": _provenance(self.repo_dir),
            "entries": {
                kid: {**meta, "key": list(meta["key"])}
                for kid, meta in self._index.items()
            },
        }
        tmp = os.path.join(self.dir, INDEX_NAME + ".tmp")
        with open(tmp, "w") as f:
            json.dump(index, f, indent=1, default=str)
        os.replace(tmp, os.path.join(self.dir, INDEX_NAME))
        return written

    # ------------------------------------------------------ eviction

    def evict(self, key: Tuple, reason: str = "") -> None:
        """Blacklist a variant from the AOT cache (resil quarantine):
        dead for this process AND removed from the on-disk index so a
        later process never serves the entry either."""
        kid = key_id(key)
        self._dead.add(kid)
        self._loaded.pop(kid, None)
        self.dropped.append((kid, reason or "evicted"))
        if self._index.pop(kid, None) is None:
            return
        self._keys.pop(kid, None)
        get_metrics().counter("route.serve.library_evictions").inc()
        path = os.path.join(self.dir, INDEX_NAME)
        if not os.path.exists(path):
            return
        try:
            with open(path) as f:
                idx = json.load(f)
            if kid in idx.get("entries", {}):
                del idx["entries"][kid]
                tmp = path + ".tmp"
                with open(tmp, "w") as f:
                    json.dump(idx, f, indent=1, default=str)
                os.replace(tmp, path)
        except (OSError, ValueError):
            pass  # in-process blacklist still holds

    # ------------------------------------------------------ dispatch

    def dispatch(self, key: Tuple, fn: Callable,
                 args: tuple, kwargs: dict):
        """Serve one window dispatch: exported executable when the
        library has this variant, jit fallback (+note) otherwise."""
        kid = key_id(key)
        if kid in self._index and kid not in self._dead:
            try:
                if self.fault_plan is not None:
                    # injected stale/truncated-entry fault: exercises
                    # the same evict-and-degrade path a real torn blob
                    # takes
                    self.fault_plan.raise_if("library.corrupt",
                                             detail=kid)
                meta = self._index[kid]
                sig = _sig_digest(fn, args, kwargs)
                if meta.get("sig") not in (None, sig):
                    raise ValueError(
                        f"signature drift {meta.get('sig')} != {sig}")
                _register_tree_serialization((args, kwargs))
                exp = self._exported(kid)
                dyn_args, dyn_kwargs = _split_dynamic(fn, args, kwargs)
                out = exp.call(*dyn_args, **dyn_kwargs)
                get_metrics().counter("route.serve.aot_hits").inc()
                return out
            except Exception:
                # evict and fall through: a broken entry must never
                # take the route down, only cost a recompile
                get_metrics().counter("route.serve.aot_errors").inc()
                self._dead.add(kid)
                self._loaded.pop(kid, None)
        self.note(key, fn, args, kwargs)
        get_metrics().counter("route.serve.jit_fallbacks").inc()
        return fn(*args, **kwargs)
