"""Pallas TPU kernels.

- block_masked_matmul: structured-pruning sparse-phase matmul
- flash_attention:     streaming-softmax attention, causal + window
- group_l2_norms:      pruning-criterion group reductions
- rglru_scan:          blocked linear recurrence (RG-LRU / SSM)

Training code does NOT import these directly: the compute-backend
dispatch layer :mod:`repro.models.ops` is the front door —
``ops.masked_matmul`` / ``ops.matmul`` / ``ops.conv`` route to
``block_masked_matmul``, ``ops.attention`` to ``flash_attention``, and
``ops.group_sq_norms_2d`` (via ``repro.core.pruning.criteria``) to
``group_l2_norms``, each selected per-run by ``ModelConfig.backend``
(``xla`` | ``pallas`` | ``ref``, env default ``$FEDPHD_BACKEND``) and
wrapped in ``custom_vjp`` where the loss path needs gradients.
``ops.pallas_interpret`` is the one place that decides between the
compiled kernels (TPU) and the Pallas interpreter (everywhere else);
the wrappers here take ``interpret`` from their caller and have no
default.

The per-kernel ``ops.py`` wrappers stay the tile-alignment gate:
off-spec shapes fall back to the ``ref.py`` oracles.  Each routing
decision is reported through :func:`note_route` — collected by
:func:`record_routes` while a program traces, and a fallback from a
compiled kernel warns — so an oracle on the chip is never silent.

``rglru_scan`` has no model caller (only ``benchmarks/kernels_bench.py``
and ``tests/test_kernels.py`` use it), and the TPU compiler refuses it:
Mosaic has no lowering for its ``dynamic_slice``.  It runs in interpret
mode only.
"""
from __future__ import annotations

import contextlib
import contextvars
import dataclasses
import warnings
from typing import Iterator, List, Optional, Tuple


@dataclasses.dataclass(frozen=True)
class Route:
    """One traced kernel call site: which kernel, how it ran
    ("compiled" | "interpret" | "fallback" to the ref oracle), and the
    operand shape that decided it."""
    kernel: str
    path: str
    shape: Tuple[int, ...]


_RECORDER: contextvars.ContextVar[Optional[List[Route]]] = \
    contextvars.ContextVar("kernel_routes", default=None)


@contextlib.contextmanager
def record_routes() -> Iterator[List[Route]]:
    """Collect a :class:`Route` for every kernel call site traced inside
    the block (routing is decided at trace time, so wrap the first call
    of a jitted program — a cached program is not re-traced)."""
    routes: List[Route] = []
    token = _RECORDER.set(routes)
    try:
        yield routes
    finally:
        _RECORDER.reset(token)


def note_route(kernel: str, shape, *, fallback: bool,
               interpret: bool) -> None:
    """Report one routing decision of a kernel wrapper."""
    path = "fallback" if fallback else (
        "interpret" if interpret else "compiled")
    shape = tuple(int(d) for d in shape)
    routes = _RECORDER.get()
    if routes is not None:
        routes.append(Route(kernel, path, shape))
    if fallback and not interpret:
        warnings.warn(f"{kernel}: shape {shape} is not tile-aligned; "
                      "running the jnp reference instead of the compiled "
                      "kernel", RuntimeWarning, stacklevel=3)
