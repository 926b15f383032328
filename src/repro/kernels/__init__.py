"""Pallas TPU kernels for the compute hot spots of the PyG 2.0 reproduction.

Each kernel lives in its own subpackage with three files:

  <name>.py  — the ``pl.pallas_call`` kernel with explicit BlockSpec tiling
  ops.py     — the jit'd public wrapper (dispatches kernel on TPU, oracle on CPU)
  ref.py     — the pure-jnp oracle used for validation and as the XLA fallback

Kernels:
  spmm             blocked-ELL sparse @ dense (message-passing fast path, C2)
  grouped_matmul   per-group GEMM {H_T W_T} (hetero projections C4 + MoE experts)
  attention        fused flash-GAT aggregation (gather -> leaky-relu ->
                   online masked softmax -> weighted accumulate) over the
                   same blocked-ELL buckets as spmm
  segment_softmax  softmax over variable-length segments (GAT oracle path,
                   explainer masks)
  flash_attention  online-softmax attention (LM prefill/train path)
"""

USE_PALLAS_ENV = "REPRO_USE_PALLAS"


def forward_only_pallas(impl, num_static: int, message: str):
    """Wrap a raw ``pallas_call`` entry point so differentiation fails fast.

    ``pallas_call`` carries no autodiff rule, so naked ``jax.grad`` through
    a raw kernel dies with an opaque trace error. The *supported* backward
    for a kernel lives in its ops-level wrapper (a hand-written
    ``jax.custom_vjp``); this helper gives the raw entry point a VJP whose
    backward raises ``NotImplementedError(message)`` instead — the message
    should name the differentiable ops-level wrapper and the
    ``REPRO_USE_PALLAS`` fallback env var.

    The first ``num_static`` arguments of ``impl`` are static/hashable
    (``nondiff_argnums``); the rest are array operands.
    """
    import functools

    import jax

    statics = tuple(range(num_static))

    @functools.partial(jax.custom_vjp, nondiff_argnums=statics)
    def wrapped(*args):
        return impl(*args)

    def fwd(*args):
        return wrapped(*args), None

    def bwd(*args):  # (*statics, residuals, cotangent)
        raise NotImplementedError(message)

    wrapped.defvjp(fwd, bwd)
    return wrapped


def pallas_kernel_name(eqn) -> str:
    """Kernel name of a ``pallas_call`` jaxpr eqn.

    Every kernel in this package passes ``name=`` to ``pallas_call``; the
    kernel function's own name (from the traced kernel jaxpr) is the
    fallback for a call that does not. The dispatch auditor and the FLOP
    cost table both key on this name.
    """
    name = eqn.params.get("name")
    if name:
        return name
    debug = getattr(eqn.params.get("jaxpr"), "debug_info", None)
    return getattr(debug, "func_name", None) or "<unnamed>"


def interpret_default() -> bool:
    """Interpret mode for kernels whose caller did not choose: True off-TPU.

    Off-TPU the kernels can only run in the Pallas interpreter; on a TPU
    they compile. ``analysis.audit_report`` counts interpret-mode launches,
    so a chip run that fell back to the interpreter is caught there.
    """
    import jax

    return jax.default_backend() != "tpu"


def use_pallas() -> bool:
    """Whether to dispatch Pallas kernels (TPU) or the jnp oracle (CPU/XLA).

    On this CPU container Pallas kernels run only in ``interpret=True`` mode,
    which we exercise in tests; production entry points leave this off so the
    XLA oracle path (itself fused by jit) is used.
    """
    import os

    import jax

    val = os.environ.get(USE_PALLAS_ENV)
    if val is not None:
        return val not in ("0", "false", "False")
    return jax.default_backend() == "tpu"
