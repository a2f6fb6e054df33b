"""Kernel backend registry, platform detection, and block-size autotuning.

Every compute op in :mod:`repro.kernels` has up to three interchangeable
implementations:

``pallas-tpu``
    The Pallas kernel compiled for the real accelerator
    (``interpret=False``). Fastest path; only valid when
    ``jax.default_backend() == "tpu"``.
``pallas-interpret``
    The same Pallas kernel run through the Pallas interpreter. Bit-faithful
    to the TPU kernel's semantics (used as the correctness harness on CPU
    containers) but orders of magnitude slower than XLA.
``ref``
    The pure-``jnp`` oracle from :mod:`repro.kernels.ref`, jitted by XLA.
    Mathematically identical contract; the fast default off-TPU.

Selection order for :func:`default_backend`:

1. ``REPRO_KERNEL_BACKEND`` env var (one of the names above) — global
   override, useful for A/B benchmarks and CI.
2. ``REPRO_PALLAS_COMPILED=1`` (legacy knob) → ``pallas-tpu``.
3. Platform detection: TPU → ``pallas-tpu``; anything else → ``ref``.

:func:`resolve` answers with exactly the backend asked for and raises
when the op has no implementation there: a ``pallas-tpu`` request is
never quietly served by the interpreter or by ``ref``.

Block-size autotune table
-------------------------
:func:`tuned_blocks` returns the block-size kwargs for a (op, shape, dtype,
backend) query. Shapes are bucketed to the next power of two so the table
stays small; exact entries win over bucketed entries, which win over the
per-op defaults. :func:`tuned_lookup` also says where the tiles came from
(``"measured"``, ``"seed"``, ``"runtime"`` or ``"default"``).

The table is PERSISTED: entries live in ``autotune_table.json`` next to
this module (override the path with ``REPRO_AUTOTUNE_TABLE``) and are
written by the real sweep in ``benchmarks/autotune_blocks.py`` — run it
with ``REPRO_REGEN_AUTOTUNE=1`` to refresh the committed table in place.
Each entry records its ``source`` ("seed" for the original hand-tuned
values, "measured" for sweep results) so stale guesses are
distinguishable from data. :func:`register_tuned` adds in-process
entries (tests, a live tuner) that win over the file.
"""
from __future__ import annotations

import functools
import json
import os
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax

KNOWN_BACKENDS = ("pallas-tpu", "pallas-interpret", "ref")

# op name -> backend name -> callable
_REGISTRY: Dict[str, Dict[str, Callable]] = {}

def register(op: str, backend: str):
    """Decorator: register ``fn`` as the ``backend`` implementation of ``op``."""
    assert backend in KNOWN_BACKENDS, backend

    def deco(fn):
        _REGISTRY.setdefault(op, {})[backend] = fn
        return fn

    return deco


@functools.lru_cache(maxsize=None)
def platform() -> str:
    """The JAX default backend platform ("cpu" | "gpu" | "tpu")."""
    return jax.default_backend()


def default_backend(op: Optional[str] = None) -> str:
    """Pick the backend for ``op`` (or globally when ``op`` is None)."""
    env = os.environ.get("REPRO_KERNEL_BACKEND", "")
    if env:
        if env not in KNOWN_BACKENDS:
            raise ValueError(
                f"REPRO_KERNEL_BACKEND={env!r}; expected one of "
                f"{KNOWN_BACKENDS}")
        return env
    if os.environ.get("REPRO_PALLAS_COMPILED", "0") == "1":
        return "pallas-tpu"
    if platform() == "tpu":
        return "pallas-tpu"
    return "ref"


def available_backends(op: str) -> Tuple[str, ...]:
    return tuple(_REGISTRY.get(op, {}))


def resolve(op: str, backend: Optional[str] = None
            ) -> Tuple[str, Callable]:
    """(backend_name, fn) for ``op`` on exactly the requested (or
    default) backend; raises ``KeyError`` if the op has none there."""
    want = backend or default_backend(op)
    if want not in KNOWN_BACKENDS:
        raise ValueError(
            f"unknown backend {want!r} for op {op!r}; expected one of "
            f"{KNOWN_BACKENDS}")
    impls = _REGISTRY.get(op, {})
    if want not in impls:
        raise KeyError(f"op {op!r} has no {want!r} implementation "
                       f"(registered: {sorted(impls)})")
    return want, impls[want]


def dispatch(op: str, *args, backend: Optional[str] = None, **kwargs):
    """Call the selected implementation of ``op``."""
    _, fn = resolve(op, backend)
    return fn(*args, **kwargs)


# ---------------------------------------------------------------------------
# Block-size autotune table
# ---------------------------------------------------------------------------

def _bucket(n: int) -> int:
    """Round up to the next power of two (shape bucketing key)."""
    b = 1
    while b < n:
        b *= 2
    return b


# Per-op defaults (used when no table entry matches). Values are the
# kwargs forwarded to the Pallas wrapper.
_DEFAULT_BLOCKS: Dict[str, Dict[str, int]] = {
    "int8_matmul": {"bm": 128, "bn": 256, "bk": 512},
    "int8_matmul_t": {"bm": 128, "bn": 512, "bk": 256},
    "int4_matmul": {"bm": 128, "bk": 512},
    "sr_requant": {"br": 256, "bc": 512},
    "blockwise_quant": {"br": 256, "bc": 512},
    "fused_qgalore_update": {"bm": 256, "bn": 512},
    "flash_attention": {"bq": 128, "bkv": 128},
}

# -- persisted autotune table ------------------------------------------------
#
# Entries are keyed (op, backend, bucketed shape, dtype) -> block kwargs
# (dtype "" matches any dtype). They live in autotune_table.json next to
# this module; benchmarks/autotune_blocks.py measures and rewrites it.
# _RUNTIME_TABLE holds in-process registrations (register_tuned) that win
# over the file.

_Key = Tuple[str, str, Tuple[int, ...], str]

_TABLE_ENV = "REPRO_AUTOTUNE_TABLE"
_TABLE_FILE = os.path.join(os.path.dirname(__file__), "autotune_table.json")

_RUNTIME_TABLE: Dict[_Key, Dict[str, int]] = {}
_Hit = Tuple[Dict[str, int], str]           # (block kwargs, source)


def table_path() -> str:
    return os.environ.get(_TABLE_ENV) or _TABLE_FILE


def _entry_key(e: Dict[str, Any]) -> _Key:
    return (e["op"], e["backend"], tuple(int(d) for d in e["shape"]),
            e.get("dtype", ""))


@functools.lru_cache(maxsize=8)
def _load_table(path: str) -> Dict[_Key, _Hit]:
    if not os.path.exists(path):
        return {}
    with open(path) as f:
        doc = json.load(f)
    table: Dict[_Key, _Hit] = {}
    for e in doc.get("entries", ()):
        table[_entry_key(e)] = ({k: int(v) for k, v in e["blocks"].items()},
                                e.get("source", "seed"))
    return table


def reload_table() -> None:
    """Drop the cached file table (after a sweep rewrote it, or a test
    pointed REPRO_AUTOTUNE_TABLE elsewhere)."""
    _load_table.cache_clear()


def load_table_entries(path: Optional[str] = None) -> List[Dict[str, Any]]:
    """The raw entry list from the persisted table (sweep merge source)."""
    p = path or table_path()
    if not os.path.exists(p):
        return []
    with open(p) as f:
        return list(json.load(f).get("entries", ()))


def save_table_entries(entries: List[Dict[str, Any]],
                       path: Optional[str] = None) -> str:
    """Write the table document; deduplicates by key (last entry wins)."""
    p = path or table_path()
    merged: Dict[_Key, Dict[str, Any]] = {}
    for e in entries:
        merged[_entry_key(e)] = e
    doc = {"version": 1,
           "entries": [merged[k] for k in sorted(merged)]}
    with open(p, "w") as f:
        json.dump(doc, f, indent=1, sort_keys=True)
        f.write("\n")
    reload_table()
    return p


def register_tuned(op: str, backend: str, shape: Tuple[int, ...],
                   blocks: Dict[str, int], dtype: str = "") -> None:
    """In-process table entry (wins over the persisted file). ``shape`` is
    bucketed here, so callers pass the raw problem shape."""
    key = (op, backend, tuple(_bucket(int(d)) for d in shape), dtype)
    _RUNTIME_TABLE[key] = dict(blocks)


def fit_block(dim: int, request: int, multiple_of: int = 1) -> int:
    """Largest tile ≤ ``request`` that divides ``dim`` (and is a multiple
    of ``multiple_of``), falling back to ``dim`` itself.

    The Pallas kernels floor-divide their grids (``grid = dim // tile``)
    without asserting divisibility, so a table/tuned tile that does not
    divide the (padded) problem dimension would silently drop the
    remainder. Every ``ops`` wrapper clamps its tile kwargs through this
    before forwarding them.

    Awkward dims (e.g. a prime sequence length) whose only small divisors
    are degenerate fall back to ``dim`` itself — one tile over that axis,
    matching the kernels' old ``min(tile, dim)`` clamp — rather than a
    grid of 1-wide tiles.

    A dim with no divisor that is a multiple of ``multiple_of`` also
    gets the whole dim: Mosaic takes a lane tile (``multiple_of=128``)
    that is 128-aligned or spans the array, e.g. the unpadded d_ff 5461.

    The returned tile is never larger than ``dim`` (nor than its
    power-of-two bucket): a table entry tuned for a big bucket cannot
    force a small decode/smoke problem to pad up to the entry's tile.
    """
    request = max(1, min(request, dim, _bucket(dim)))
    best = next((d for d in range(request, 0, -1)
                 if dim % d == 0 and d % multiple_of == 0), dim)
    if best * 4 <= request:
        return dim
    return best


def pick_tile(dim: int, request: int, multiple_of: int = 8) -> int:
    """Tile size for a dimension the caller is about to PAD: the smallest
    multiple of ``multiple_of`` covering ``dim``, capped at ``request``.

    :func:`fit_block` fits a tile *into* a fixed (already padded)
    dimension; this is the converse for the wrappers that pad rows up to
    the tile. Picking the tile from the TRUE dimension first fixes the
    tail-block waste on exactly the shapes serving hits: a 1-row decode
    matmul pads to one 8-row tile (the f32 sublane) instead of the old
    hard-coded 128-row boundary, and a 100-row prefill pads to 104 rows
    instead of 128. The caller then pads ``dim`` up to a multiple of the
    returned tile, so the Pallas grid division is exact.
    """
    need = -(-max(dim, 1) // multiple_of) * multiple_of
    return max(multiple_of, min(max(request, multiple_of), need))


def tuned_lookup(op: str, shape: Tuple[int, ...], dtype: str = "",
                 backend: Optional[str] = None) -> _Hit:
    """(block-size kwargs, source) for ``op`` on a problem of ``shape``.

    ``shape`` is the op's 2-D problem footprint (e.g. the weight matrix
    (M, N) for the fused update, (rows, K) for the INT8 matmuls). Lookup
    order: in-process registrations (:func:`register_tuned`, source
    ``"runtime"``) → the persisted table (exact (bucketed shape, dtype),
    then (bucketed shape, any dtype); the entry's ``source``) → per-op
    defaults (``"default"``).
    """
    backend = backend or default_backend(op)
    bshape = tuple(_bucket(int(d)) for d in shape)
    table = _load_table(table_path())
    for dt in (dtype, ""):
        key = (op, backend, bshape, dt)
        if key in _RUNTIME_TABLE:
            return dict(_RUNTIME_TABLE[key]), "runtime"
        if key in table:
            blocks, source = table[key]
            return dict(blocks), source
    return dict(_DEFAULT_BLOCKS.get(op, {})), "default"


def tuned_blocks(op: str, shape: Tuple[int, ...],
                 dtype: str = "", backend: Optional[str] = None
                 ) -> Dict[str, int]:
    """Block-size kwargs for ``op`` on a problem of ``shape``
    (:func:`tuned_lookup` without the source)."""
    return tuned_lookup(op, shape, dtype, backend)[0]
