"""Pluggable execution backends for the quantized primitives.

The paper's central system claim is that the HPDP is a swappable
*mathematical backend*: "the AI framework executes workloads directly on
this co-processor without requiring additional hardware-specific coding".
This module is that claim as an API.  Every quantized primitive (qmatmul,
qconv2d) registers interchangeable implementations behind one registry:

  ref     independent jnp oracle (int32-upcast math / explicit tap loop) —
          the Fig.-4 "PyTorch reference" role
  jnp     XLA-native int8 dot_general / conv_general_dilated — the fleet
          default on CPU and the fastest path XLA fuses on its own
  pallas  the Pallas TPU kernels (interpreted when lowered for the CPU) —
          the paper's actual co-processor path, including the fused ABFT
          checksum

The registry's uniform signature is **accumulator-level**: every backend
returns the raw int32 accumulator (and, for the checksummed entry, the
in-path ABFT check vector), so campaign ``inject`` hooks and the
Huang–Abraham verification compose with *any* backend — the dependability
layer is written once against a ``Backend`` handle and never mentions a
specific execution engine again.

Selection precedence (most specific wins):

  1. per-call   ``dependable_qmatmul(..., backend="pallas")``
  2. per-layer  model configs carry a backend (``ArchConfig.backend``,
                per-layer lists in ``models/shipdet.forward``)
  3. global     ``set_default_backend`` / ``use_backend`` context manager

All three accept either a backend name or a ``Backend`` instance.  Because
the hot path is integer (int8 × int8 → int32, exact mod 2^32), every
registered backend is **bit-identical** — the parity tests in
``tests/test_backend.py`` enforce it, and a campaign certified on one
backend transfers to another only because this property holds.
"""
from __future__ import annotations

import contextlib
import dataclasses
import threading
from typing import Callable, Dict, List, Optional, Tuple, Union

import jax

BackendLike = Union[str, "Backend", None]


@dataclasses.dataclass(frozen=True)
class Backend:
    """One execution engine for the quantized primitives.

    All entries are accumulator-level (no bias, no requantization — those
    are policy-layer algebra shared by every backend):

      matmul_acc(x_q i8 (M,K), w_q i8 (K,N)) -> i32 (M,N)
          the raw dot X·W (zero-point correction applied downstream)
      matmul_acc_checksum(x_q, w_q, w_check i32 (K,)) -> (acc, want (M,))
          acc as above plus the ABFT check vector want = X·w_check,
          computed *in the execution path* (fused into the kernel on the
          pallas backend)
      conv_acc(x_q i8 NHWC, x_zp i32, w_q i8 HWIO, stride, padding)
          -> i32 (N,OH,OW,Cout): conv(x_q - x_zp, w_q)
      conv_acc_checksum(x_q, x_zp, w_q, w_check i32 (KH,KW,Cin,1),
                        stride, padding) -> (acc, want (N,OH,OW))

    The attention entries cover the one float hot kernel (flash attention;
    optional so out-of-tree integer-only backends stay valid):

      attn(q (B,H,S,hd), k, v (B,KV,S,hd), *, causal, window)
          -> (B,H,S,hd): fused causal/sliding-window attention
      attn_checksum(q, k, v, *, causal, window) -> (out, check, csum)
          out as above; ``check`` (B,H,S) f32 is an independently accumulated
          rowsum_hd(out) column (tolerance-verified compute-path cover);
          ``csum`` (B,H,S) u32 is the exact mod-2^32 bit checksum of the
          emitted output rows (bit-exact output-integrity cover) — both
          fused into the kernel on the pallas backend
    """

    name: str
    matmul_acc: Callable[..., jax.Array]
    matmul_acc_checksum: Callable[..., Tuple[jax.Array, jax.Array]]
    conv_acc: Callable[..., jax.Array]
    conv_acc_checksum: Callable[..., Tuple[jax.Array, jax.Array]]
    description: str = ""
    attn: Optional[Callable[..., jax.Array]] = None
    attn_checksum: Optional[
        Callable[..., Tuple[jax.Array, jax.Array, jax.Array]]] = None


_REGISTRY: Dict[str, Backend] = {}
# thread-local so `use_backend` nesting in concurrent test runners can't
# bleed a temporary default across threads
_STATE = threading.local()
_GLOBAL_DEFAULT = "jnp"


def register_backend(backend: Backend, *, overwrite: bool = False) -> Backend:
    """Add a backend to the registry (how out-of-tree engines plug in)."""
    if backend.name in _REGISTRY and not overwrite:
        raise ValueError(f"backend {backend.name!r} already registered "
                         f"(pass overwrite=True to replace)")
    _REGISTRY[backend.name] = backend
    return backend


def _ensure_builtins() -> None:
    # The built-in implementations live next to the kernels they wrap;
    # importing the dispatch module registers them.  Lazy so core/ never
    # imports kernels/ at module load (no cycle).
    if "jnp" not in _REGISTRY:
        from repro.kernels import dispatch  # noqa: F401  (registers on import)


def available_backends() -> List[str]:
    """Registered backend names, built-ins guaranteed present."""
    _ensure_builtins()
    return sorted(_REGISTRY)


def get_backend(name: str) -> Backend:
    _ensure_builtins()
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(f"unknown backend {name!r}; known: {sorted(_REGISTRY)}"
                       ) from None


def default_backend() -> str:
    """The currently active global default (innermost ``use_backend`` wins)."""
    stack = getattr(_STATE, "stack", None)
    return stack[-1] if stack else _GLOBAL_DEFAULT


def set_default_backend(name: str) -> None:
    """Set the process-wide default backend (validated)."""
    global _GLOBAL_DEFAULT
    get_backend(name)
    _GLOBAL_DEFAULT = name


@contextlib.contextmanager
def use_backend(name: str):
    """Scoped global selection: every op inside the block that does not get
    a more specific (per-layer / per-call) choice runs on ``name``."""
    get_backend(name)
    stack = getattr(_STATE, "stack", None)
    if stack is None:
        stack = _STATE.stack = []
    stack.append(name)
    try:
        yield
    finally:
        stack.pop()


def resolve(backend: BackendLike = None) -> Backend:
    """Per-call > per-layer > global precedence collapses to one rule: the
    most specific non-None choice reaches this function first."""
    if isinstance(backend, Backend):
        return backend
    return get_backend(backend if backend is not None else default_backend())
