"""One resolver for every ``REPRO_*`` runtime knob (DESIGN.md §18).

Historically each subsystem read its own environment variable at its own
call site (``engine.resolve_backend``, ``resident.resident_enabled``,
``kernels.resolve_interpret``, ``fused_superstep.fused_enabled``,
``obs.metrics.obs_enabled``, ...) with subtly different parsing rules.
This module is now the single place those knobs are declared, parsed and
resolved; the historical module-level functions remain as thin delegates.

Resolution order for every knob (``Settings.resolve`` and :func:`setting`):

    environment variable  >  constructor/keyword override  >  default

Environment reads happen *per call* — a dict get, not a cached import-time
snapshot — so tests and long-lived services can flip a knob mid-process
(e.g. ``REPRO_PARALLEL_MAINT=0`` to fall back to the serial maintenance
oracle) without re-importing anything.

Knobs
-----
``backend``            ``REPRO_BACKEND``            default compute backend name
``device_resident``    ``REPRO_DEVICE_RESIDENT``    device-resident fixpoint (=0 off)
``resident_chunk``     ``REPRO_RESIDENT_CHUNK``     lax.scan passes per round-trip
``pallas_fused``       ``REPRO_PALLAS_FUSED``       fused single-kernel superstep
``pallas_interpret``   ``REPRO_PALLAS_INTERPRET``   must match the platform (CPU only)
``fused_block_edges``  ``REPRO_FUSED_BLOCK_EDGES``  kernel tile size (None = 512)
``obs``                ``REPRO_OBS``                telemetry registry on/off
``parallel_maint``     ``REPRO_PARALLEL_MAINT``     grouped batched maintenance
"""
from __future__ import annotations

import os
from dataclasses import dataclass, fields

__all__ = [
    "Settings",
    "get_settings",
    "setting",
    "enable_compile_cache",
    "ENV_VARS",
    "DEFAULT_RESIDENT_CHUNK",
]

#: knob name -> environment variable
ENV_VARS = {
    "backend": "REPRO_BACKEND",
    "device_resident": "REPRO_DEVICE_RESIDENT",
    "resident_chunk": "REPRO_RESIDENT_CHUNK",
    "pallas_fused": "REPRO_PALLAS_FUSED",
    "pallas_interpret": "REPRO_PALLAS_INTERPRET",
    "fused_block_edges": "REPRO_FUSED_BLOCK_EDGES",
    "obs": "REPRO_OBS",
    "parallel_maint": "REPRO_PARALLEL_MAINT",
}

#: lax.scan passes per host round-trip (mirrored by resident.DEFAULT_CHUNK)
DEFAULT_RESIDENT_CHUNK = 8

_FALSY = ("0", "false", "no", "off")


def _parse_flag(raw: str):
    """Generous boolean: anything but the falsy spellings is on."""
    return raw.strip().lower() not in _FALSY


def _parse_strict_zero(raw: str):
    """Historical ``!= "0"`` parsing (device_resident, obs)."""
    return raw != "0"


def _parse_chunk(raw: str):
    try:
        return max(1, int(raw))
    except ValueError:
        return DEFAULT_RESIDENT_CHUNK


def _parse_block_edges(raw: str):
    raw = raw.strip()
    if not raw:
        return None  # empty string == unset (historical behavior)
    return int(raw)  # range-validated at the use site (>= 8)


_PARSERS = {
    "backend": lambda raw: raw,
    "device_resident": _parse_strict_zero,
    "resident_chunk": _parse_chunk,
    "pallas_fused": _parse_flag,
    "pallas_interpret": _parse_flag,
    "fused_block_edges": _parse_block_edges,
    "obs": _parse_strict_zero,
    "parallel_maint": _parse_flag,
}

_UNSET = object()


def setting(name: str, override=_UNSET):
    """Resolve one knob: env (if set) > ``override`` (if given, non-None) >
    dataclass default.  This is the fast path used by the historical
    accessor functions — it reads exactly one environment variable."""
    raw = os.environ.get(ENV_VARS[name])
    if raw is not None:
        parsed = _PARSERS[name](raw)
        if parsed is not None:
            return parsed
    if override is not _UNSET and override is not None:
        return override
    return _DEFAULTS[name]


@dataclass(frozen=True)
class Settings:
    """Resolved runtime configuration.

    Construct directly for explicit values, or via :meth:`resolve` /
    :func:`get_settings` to apply the env > override > default order.
    Instances are frozen: a component handed a ``Settings`` object sees a
    consistent snapshot for its lifetime, while code that wants live env
    semantics calls :func:`get_settings` (or :func:`setting`) per use.
    """

    backend: str = "numpy"
    device_resident: bool = True
    resident_chunk: int = DEFAULT_RESIDENT_CHUNK
    pallas_fused: bool = True
    pallas_interpret: bool | None = None  # None: the platform decides
    fused_block_edges: int | None = None  # None: the kernel's 512 tile
    obs: bool = True
    parallel_maint: bool = True

    @classmethod
    def resolve(cls, **overrides) -> "Settings":
        """Build a Settings snapshot with env > override > default per knob.

        ``None`` overrides mean "not specified" for every knob except the
        genuinely tri-state ``pallas_interpret``/``fused_block_edges``,
        where ``None`` is also the default, so the distinction is moot.
        """
        unknown = set(overrides) - set(ENV_VARS)
        if unknown:
            raise TypeError(f"unknown settings: {sorted(unknown)}")
        vals = {k: setting(k, overrides.get(k, _UNSET)) for k in ENV_VARS}
        return cls(**vals)

    def env(self) -> dict[str, str]:
        """Render as environment-variable assignments (for subprocesses)."""
        out = {}
        for f in fields(self):
            v = getattr(self, f.name)
            if v is None:
                continue
            if isinstance(v, bool):
                out[ENV_VARS[f.name]] = "1" if v else "0"
            else:
                out[ENV_VARS[f.name]] = str(v)
        return out


_DEFAULTS = {f.name: f.default for f in fields(Settings)}


def get_settings(**overrides) -> Settings:
    """The module-level resolver: ``Settings.resolve`` with live env reads."""
    return Settings.resolve(**overrides)


def enable_compile_cache(root: str) -> str:
    """Turn on JAX's persistent compilation cache for an entry point.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself and this
    sets nothing; otherwise the cache lives at ``<root>/.jax_cache``, a
    fixed path so that later runs of the same checkout find it.  Called by
    the scripts (``chip_smoke.py``, ``benchmarks/*``), never on import.
    Returns the cache directory.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        import jax

        path = os.path.join(os.path.abspath(root), ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path
