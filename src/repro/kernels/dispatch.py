"""Backend-aware kernel dispatch policy (the compiled data plane's seam).

Every coding kernel used to decide how to run with a scattered
``interpret = jax.default_backend() != "tpu"`` check — which silently ran
*interpret-mode* Pallas on GPU (where the Triton lowering compiles fine)
and on every CPU CI runner (where interpret mode is ~15x slower than
numpy).  This module is the single policy those call sites share now:

* **TPU / GPU** -> compiled Pallas (``interpret=False``): the batched
  grids lower natively (Mosaic on TPU, Triton on GPU).
* **CPU** -> an XLA-jitted GF(2^8) path (``xla_gf256``): bit-plane /
  log-exp-table formulations compiled by XLA CPU — no interpret tax, and
  measurably faster than the numpy oracle (see ``benchmarks/
  kernels_bench.py`` compiled-vs-interpret-vs-numpy rows).  Kernels with
  no XLA twin (none today) would fall back to interpret explicitly.
* **Interpret mode** is an escape hatch only: ``$MEMEC_INTERPRET=1``
  forces it everywhere (debugging kernel bodies on any backend), and an
  explicit ``interpret=True`` argument forces it per call (tests).

``decide()`` returns the chosen path; engines surface it through
``CodingEngine.describe()``/``stats()`` so a run can always answer "did
I actually compile?".  ``benchmarks/kernels_bench.py`` fails loudly if
the policy lands on interpret without ``$MEMEC_INTERPRET`` being set.
"""
from __future__ import annotations

import dataclasses
import os
import pathlib
import re

import jax

# dispatch paths
PALLAS = "pallas-compiled"   # pl.pallas_call, interpret=False
XLA = "xla-compiled"         # jitted jnp GF(2^8) formulation (CPU)
INTERPRET = "interpret"      # pl.pallas_call, interpret=True

_TRUTHY = ("1", "true", "yes", "on")

REPO_ROOT = pathlib.Path(__file__).resolve().parents[3]

# fixed compile-cache location when $JAX_COMPILATION_CACHE_DIR is unset
# (a fixed path: a cache directory that moves between runs never hits)
DEFAULT_CACHE_DIR = REPO_ROOT / ".jax_cache"

# removed from source file names in lowered code: a Pallas kernel's body
# keeps its source locations, and so its cache key, inside the compiled
# call, so without this a checkout at another path never hits the cache
SOURCE_PREFIX_RE = "^" + re.escape(str(REPO_ROOT)) + "/"


@dataclasses.dataclass(frozen=True)
class Decision:
    """How a kernel call should run.

    ``path``: PALLAS | XLA | INTERPRET; ``interpret``: the flag handed to
    ``pl.pallas_call`` when the path is Pallas-shaped (PALLAS/INTERPRET —
    XLA-path callers never reach a ``pallas_call``).
    """
    path: str

    @property
    def interpret(self) -> bool:
        return self.path == INTERPRET

    @property
    def compiled(self) -> bool:
        return self.path != INTERPRET


def backend() -> str:
    """The active jax backend (``cpu`` | ``gpu`` | ``tpu``)."""
    return jax.default_backend()


def interpret_forced() -> bool:
    """``$MEMEC_INTERPRET`` truthy — the explicit interpret escape hatch
    (read per call so tests can flip it with monkeypatch)."""
    return os.environ.get("MEMEC_INTERPRET", "").strip().lower() in _TRUTHY


def decide(interpret: bool | None = None, *, xla_ok: bool = True) -> Decision:
    """Resolve the dispatch path for one kernel call.

    ``interpret`` is the per-call override kernels have always accepted:
    ``True`` forces interpret mode, ``False`` forces compiled Pallas
    (raising on backends with no Pallas lowering — an explicit ask), and
    ``None`` defers to the policy.  ``xla_ok=False`` marks kernels that
    have no XLA twin; on CPU those fall back to interpret.
    """
    if interpret is True:
        return Decision(INTERPRET)
    if interpret is False:
        return Decision(PALLAS)
    if interpret_forced():
        return Decision(INTERPRET)
    if backend() in ("tpu", "gpu"):
        return Decision(PALLAS)
    return Decision(XLA) if xla_ok else Decision(INTERPRET)


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    ``$JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own setting and is
    left alone; otherwise the cache lives at ``<repo>/.jax_cache``.  The
    minimum compile time to cache drops to zero, since each coding kernel
    compiles in well under JAX's one-second default, and source file names
    lose the checkout's own prefix (``SOURCE_PREFIX_RE``), so that every
    checkout of the same tree shares the entries.  Entry points call this
    once at start-up; importing the package never does.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_hlo_source_file_canonicalization_regex",
                      SOURCE_PREFIX_RE)
    return path


def describe() -> dict:
    """Policy snapshot for ``engine.describe()`` / bench provenance."""
    d = decide()
    return {
        "backend": backend(),
        "path": d.path,
        "interpret_forced": interpret_forced(),
    }
