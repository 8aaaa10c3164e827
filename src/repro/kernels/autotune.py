"""Kernel-agnostic autotune substrate shared by every Pallas kernel family.

``kernels/conv3d/tiles.py`` grew the full treatment — measured candidate
sweeps, an in-memory registry, a persistent on-disk cache keyed by
(signature, dtype, device kind) — but all of it was welded to conv tile
configs.  This module is that machinery with the conv specifics factored
out, so flash-attention block sizes and SSD scan chunk lengths tune
through the SAME registry, cache files, and measurement clock.

A kernel family plugs in by registering a :class:`KernelSpec`:

- ``kinds`` — the signature kind-tags the family owns (conv3d owns
  ``conv``/``conv_t``/``dw``/``dw_t``; attention owns ``attn``; the SSD
  scan owns ``ssm``).
- ``schedule_cls`` — a frozen dataclass of schedule parameters
  (``ConvTiles``, ``AttnBlocks``, ``ScanChunks``); its fields are what
  the JSON cache stores.
- ``default`` / ``candidates`` — the shape heuristic and the sweep space.
- ``build`` — constructs representative arrays + a timed runner for a
  signature, used by :func:`autotune_signature`.

Resolution order everywhere: exact in-memory registration, then the
dtype-free base signature, then the on-disk cache for the current device
(warm-loaded once per process), then the family's heuristic default.
The cache file format is unchanged from the conv3d-only era — existing
``results/autotune/<device_kind>.json`` entries keep loading bit-for-bit.
"""
from __future__ import annotations

import dataclasses
import json
import os
import time
from typing import Callable, Dict, Iterable, List, Optional, Tuple

_HERE = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
DEFAULT_CACHE_DIR = os.path.join(_HERE, "results", "autotune")

Signature = Tuple  # (kind, *shape-fields[, dtype-name])


@dataclasses.dataclass(frozen=True)
class KernelSpec:
    """How one kernel family participates in the shared autotuner.

    ``sig_len`` counts the signature fields BEFORE the optional trailing
    dtype name, so dtype-qualified lookups can fall back to their base.
    ``build(sig)`` returns ``run(schedule, steps=, repeats=) -> seconds``
    over representative arrays; it is only called by the measurement
    driver, never on the inference path.  ``parse`` may override the
    generic string→signature decoder for exotic key layouts.
    """
    family: str
    kinds: Tuple[str, ...]
    schedule_cls: type
    sig_len: int
    default: Callable[[Signature], object]
    candidates: Callable[[Signature], List[object]]
    build: Optional[Callable[[Signature], Callable]] = None
    parse: Optional[Callable[[List[str]], Optional[Signature]]] = None


_FAMILIES: Dict[str, KernelSpec] = {}
_KIND_TO_FAMILY: Dict[str, str] = {}
_REGISTRY: Dict[Signature, object] = {}
_CACHE_LOADED: set = set()      # device kinds whose disk cache was merged


def register_kernel(spec: KernelSpec) -> None:
    """Idempotently install a family's spec (latest registration wins)."""
    _FAMILIES[spec.family] = spec
    for kind in spec.kinds:
        _KIND_TO_FAMILY[kind] = spec.family


def _ensure_families() -> None:
    """Import every in-tree kernel family's tune module.

    Cache loading parses keys by their kind tag, and the warm-load flag is
    per-device-kind, not per-family — if only one family were imported
    when the cache loads, the other families' entries would be silently
    dropped for the rest of the process.  Lazy (and import-error-tolerant:
    a family with a missing optional dep just doesn't join the registry).
    """
    import importlib
    for mod in ("repro.kernels.conv3d.tiles",
                "repro.kernels.flash_attention.tune",
                "repro.kernels.flash_attention.decode",
                "repro.kernels.ssm_scan.tune"):
        try:
            importlib.import_module(mod)
        except ImportError:
            pass


def spec_for(sig: Signature) -> KernelSpec:
    _ensure_families()
    family = _KIND_TO_FAMILY.get(sig[0])
    if family is None:
        raise KeyError(f"no kernel family registered for kind {sig[0]!r} "
                       f"(known: {sorted(_KIND_TO_FAMILY)})")
    return _FAMILIES[family]


def dtype_name(dtype) -> str:
    return getattr(dtype, "name", None) or getattr(dtype, "__name__", None) \
        or str(dtype)


def register_schedule(sig: Signature, schedule) -> None:
    _REGISTRY[sig] = schedule


def clear_registry() -> None:
    _REGISTRY.clear()
    _CACHE_LOADED.clear()


def _base_sig(sig: Signature, spec: KernelSpec) -> Optional[Signature]:
    return sig[:spec.sig_len] if len(sig) == spec.sig_len + 1 else None


def get_schedule(sig: Signature):
    """Registered schedule if present, else the family heuristic.

    Resolution: exact in-memory registration (a dtype-qualified signature
    falls back to its dtype-free base, so hand-registered entries keep
    working), then the on-disk autotune cache for the current device
    (warm-loaded once per process), then the family's ``default``.
    """
    hit = _REGISTRY.get(sig)
    if hit is not None:
        return hit
    spec = spec_for(sig)
    base = _base_sig(sig, spec)
    if base is not None:
        hit = _REGISTRY.get(base)
        if hit is not None:
            return hit
    kind = _device_kind()
    if kind not in _CACHE_LOADED:
        load_cache(kind=kind)
        hit = _REGISTRY.get(sig) or (
            _REGISTRY.get(base) if base is not None else None)
        if hit is not None:
            return hit
    return spec.default(sig)


def default_schedule(sig: Signature):
    return spec_for(sig).default(sig)


def candidate_schedules(sig: Signature) -> List:
    return spec_for(sig).candidates(sig)


def autotune(sig: Signature, measure: Callable[[object], float],
             candidates: Optional[Iterable] = None):
    """Measure ``candidates`` (seconds, lower is better), register the best.

    ``measure`` runs the kernel with a given schedule and returns its
    cost; the driver below passes timed executions, tests pass analytic
    stand-ins.
    """
    if candidates is None:
        candidates = candidate_schedules(sig)
    best, best_cost = None, float("inf")
    for cand in candidates:
        cost = measure(cand)
        if cost < best_cost:
            best, best_cost = cand, cost
    assert best is not None, "autotune needs at least one candidate"
    register_schedule(sig, best)
    return best


# ---------------------------------------------------------------------------
# measurement driver: time candidates on the live device
# ---------------------------------------------------------------------------


def time_min_of_repeats(fn, args, steps: int = 3, repeats: int = 3) -> float:
    """Seconds per execution of ``fn(*args)``: warmup + min over
    ``repeats`` timed batches of ``steps`` calls.  The min is the
    least-contended execution — robust to scheduler noise on shared
    hosts.  Shared by the autotune driver and the kernel benchmarks so
    winners and recorded numbers come from the same clock."""
    import jax
    out = fn(*args)                       # compile + warmup
    jax.block_until_ready(out)
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        for _ in range(steps):
            out = fn(*args)
        jax.block_until_ready(out)
        best = min(best, (time.perf_counter() - t0) / steps)
    return best


def _device_kind() -> str:
    import jax
    try:
        return jax.devices()[0].device_kind.replace(" ", "_")
    except Exception:                     # no backend yet — be permissive
        return "unknown"


def autotune_signature(sig: Signature, *, steps: int = 3,
                       cache_dir: Optional[str] = None,
                       use_cache: bool = True) -> Tuple[object, int]:
    """Tune one signature on the live device.

    Returns ``(best, n_measured)`` — ``n_measured == 0`` when the on-disk
    cache already held an entry (the warm-start the CLI asserts on).
    Winners are registered in-memory AND persisted.
    """
    spec = spec_for(sig)
    if use_cache:
        load_cache(cache_dir=cache_dir)
        if sig in _REGISTRY:
            return _REGISTRY[sig], 0
    if spec.build is None:
        raise ValueError(f"family {spec.family!r} has no measurement "
                         "builder; pass schedules via register_schedule")
    run = spec.build(sig)
    measured = [0]

    def measure(schedule) -> float:
        measured[0] += 1
        return run(schedule, steps=steps)

    best = autotune(sig, measure)
    save_cache(cache_dir=cache_dir)
    return best, measured[0]


# ---------------------------------------------------------------------------
# trace-time interpret default (shared by every kernel's public wrapper)
# ---------------------------------------------------------------------------


def default_interpret() -> bool:
    """Pallas ``interpret`` default: emulate everywhere except real TPUs.

    ``REPRO_PALLAS_INTERPRET`` overrides (unset/empty = auto; ``0`` /
    ``false`` / ``no`` force compiled, anything else forces interpret).
    Resolved at trace time, so a wrapper default of ``None`` freezes the
    decision into the jaxpr exactly once per trace.
    """
    env = os.environ.get("REPRO_PALLAS_INTERPRET", "")
    if env:
        return env.lower() not in ("0", "false", "no")
    import jax
    return jax.default_backend() != "tpu"


def resolve_interpret(interpret: Optional[bool]) -> bool:
    return default_interpret() if interpret is None else bool(interpret)


def default_use_pallas(env_var: str) -> bool:
    """Launcher-level kernel-routing default: OFF on every backend unless
    the flag family's env var is ``1`` / ``true`` / ``yes`` / ``on``.
    Off because the v5e compiler still refuses the attention and
    SSM kernels at the LM widths (``tests/test_tpu_compile.py`` pins each
    refusal); a family's default may turn on once its AOT cases compile.
    Resolved once at launcher startup and frozen into the ArchConfig, so
    the routing decision is trace-time static like every other config
    field.
    """
    return os.environ.get(env_var, "").lower() in ("1", "true", "yes", "on")


# ---------------------------------------------------------------------------
# on-disk persistence (results/autotune/<device_kind>.json)
# ---------------------------------------------------------------------------


def cache_path(kind: Optional[str] = None,
               cache_dir: Optional[str] = None) -> str:
    env_dir = os.environ.get("REPRO_AUTOTUNE_DIR", "")
    base = cache_dir or env_dir or DEFAULT_CACHE_DIR
    return os.path.join(base, f"{kind or _device_kind()}.json")


def _sig_to_str(sig: Signature) -> str:
    parts = []
    for field in sig:
        if isinstance(field, tuple):
            parts.append("x".join(str(int(d)) for d in field))
        else:
            parts.append(str(field))
    return "|".join(parts)


def _generic_parse(spec: KernelSpec, parts: List[str]) -> Optional[Signature]:
    """Decode ``kind|field|...[|dtype]``: ints stay ints, ``x``-joined
    runs become tuples, a trailing non-numeric field is the dtype name."""
    if len(parts) not in (spec.sig_len, spec.sig_len + 1):
        return None
    sig: list = [parts[0]]
    try:
        for p in parts[1:spec.sig_len]:
            if "x" in p:
                sig.append(tuple(int(d) for d in p.split("x")))
            else:
                sig.append(int(p))
    except ValueError:                    # hand-edited/truncated key
        return None
    if len(parts) == spec.sig_len + 1:
        sig.append(parts[-1])
    return tuple(sig)


def _sig_from_str(s: str) -> Optional[Signature]:
    parts = s.split("|")
    if not parts:
        return None
    _ensure_families()
    family = _KIND_TO_FAMILY.get(parts[0])
    if family is None:
        return None
    spec = _FAMILIES[family]
    if spec.parse is not None:
        return spec.parse(parts)
    return _generic_parse(spec, parts)


def save_cache(kind: Optional[str] = None,
               cache_dir: Optional[str] = None) -> str:
    """Persist the in-memory registry for this device kind (merging over
    whatever the file already holds, so concurrent tuners compose)."""
    path = cache_path(kind, cache_dir)
    entries = {}
    if os.path.exists(path):
        try:
            with open(path) as f:
                entries = json.load(f).get("tiles", {})
        except (json.JSONDecodeError, OSError,
                AttributeError, TypeError):
            entries = {}                  # corrupt cache: overwrite
        if not isinstance(entries, dict):
            entries = {}                  # e.g. {"tiles": 0}
    for sig, schedule in _REGISTRY.items():
        entries[_sig_to_str(sig)] = dataclasses.asdict(schedule)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    payload = {"device_kind": kind or _device_kind(),
               "version": 1, "tiles": entries}
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(payload, f, indent=1, sort_keys=True)
    os.replace(tmp, path)
    return path


def load_cache(kind: Optional[str] = None,
               cache_dir: Optional[str] = None) -> int:
    """Merge the on-disk cache into the registry (in-memory entries win).

    A missing, corrupt, or shape-mismatched file is NOT an error — the
    kernels must never fail because a cache went stale; they fall back to
    the family default.  Keys whose kind tag no family claims are skipped
    (a cache written by a newer tree stays loadable).  Returns the number
    of entries merged.
    """
    _ensure_families()
    kind = kind or _device_kind()
    if cache_dir is None:
        # only a DEFAULT-location load satisfies get_schedule's warm-load;
        # an explicit scratch cache_dir must not suppress it
        _CACHE_LOADED.add(kind)
    path = cache_path(kind, cache_dir)
    if not os.path.exists(path):
        return 0
    try:
        with open(path) as f:
            payload = json.load(f)
        entries = payload["tiles"]
        assert isinstance(entries, dict)
    except (json.JSONDecodeError, OSError, KeyError,
            AssertionError, TypeError):
        return 0                          # corrupt cache -> heuristic
    n = 0
    for key, val in entries.items():
        sig = _sig_from_str(key)
        if sig is None or not isinstance(val, dict):
            continue
        spec = _FAMILIES[_KIND_TO_FAMILY[sig[0]]]
        known = {f.name for f in dataclasses.fields(spec.schedule_cls)}
        try:
            schedule = spec.schedule_cls(
                **{k: v for k, v in val.items() if k in known})
        except TypeError:
            continue
        if sig not in _REGISTRY:          # in-memory registrations win
            _REGISTRY[sig] = schedule
            n += 1
    return n


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m
