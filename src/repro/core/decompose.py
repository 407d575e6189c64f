"""Unified public API for the paper's decomposition technique.

``conv2d`` dispatches to dense / dilated / transposed execution with the
decomposition applied automatically — this is the entry point the model zoo
(ENet, ESPNet, conv frontends) uses, so the technique is a first-class
framework feature rather than a demo.

The engine is fully general: transposed convolutions accept any square
``(kernel, stride, output_padding)`` via the programmatic parity schedule
(paper §II-C generalised — see DESIGN.md §3), dilated convolutions accept
any ``stride`` via the output-class schedule (DESIGN.md §2c), and dense
convolutions accept rectangular kernels (ENet's 5x1/1x5 asymmetric pair).
``backend`` selects the execution engine: ``"xla"`` composes ``lax``
convolutions, ``"pallas"`` runs the fused Pallas kernels in
:mod:`repro.kernels`.

Two cross-cutting features ride the dispatcher (DESIGN.md §7):

* **fused epilogues** — ``epilogue=EpilogueSpec(...)`` with matching
  ``scale``/``shift``/``alpha``/``residual`` operands folds BN, PReLU and a
  residual add into the kernel's output pass (the XLA backend applies the
  identical :func:`repro.kernels.epilogue.apply_reference` oracle post-conv,
  so both backends compute the same function);
* **autotuned tiling** — when ``th``/``tc`` are left unset, the pallas tile
  shape is resolved per layer geometry through
  :mod:`repro.kernels.autotune` (cached sweep; defaults on a cold miss).

Each call runs under one ``jax.named_scope`` of :mod:`repro.obs`
(``engine.dense``, ``engine.dilated`` or ``engine.transposed``, by geometry
class), so a profile puts every kernel and layout pass of the call, and of
its backward, under one engine.

``conv2d`` is fully differentiable on both backends: the XLA paths are lax
compositions, and every fused Pallas kernel registers a ``jax.custom_vjp``
whose backward re-enters the engine through the adjoint symmetry — the
input-gradient of a strided dense conv is a transposed conv, of a transposed
conv a strided dense conv, of a dilated conv the same dilated conv; weight
gradients are tap-gather correlations (DESIGN.md §6,
:mod:`repro.core.adjoints`); fused epilogues differentiate by adjoint
re-entry of the conv∘epilogue composition.  The pallas backend is
first-order differentiable (``jax.custom_vjp`` is not
forward-differentiable).
"""

from __future__ import annotations

import jax

from repro import obs
from repro.core import dilated as _dil
from repro.core import transposed as _tr
from repro.kernels.epilogue import EpilogueSpec, apply_reference, pack_args
from repro.kernels.util import canon_dtype


def _resolve_tiles(kind: str, x, w, stride: int, dilation: int,
                   th: int | None, tc: int | None, padding=None,
                   output_padding: int | None = None,
                   epilogue: EpilogueSpec | None = None) -> tuple[int, int]:
    """Fill unset tile dims from the autotune table (DESIGN.md §7).

    The epilogue spec rides into the cache key — fused operands change the
    kernel's VMEM footprint, so each configuration tunes separately.
    """
    from repro.kernels import autotune

    if th is not None and tc is not None:
        return th, tc
    tth, ttc = autotune.get_tiles(kind, tuple(x.shape), tuple(w.shape),
                                  stride=stride, dilation=dilation,
                                  dtype=x.dtype, padding=padding,
                                  output_padding=output_padding,
                                  epilogue=epilogue)
    return (tth if th is None else th), (ttc if tc is None else tc)


def conv2d(
    x: jax.Array,
    w: jax.Array,
    *,
    stride: int = 1,
    dilation: int = 1,
    transposed: bool = False,
    padding: int | None = None,
    output_padding: int = 0,
    decomposed: bool = True,
    strategy: str = "batched",
    backend: str = "xla",
    interpret: bool | None = None,
    epilogue: EpilogueSpec | None = None,
    scale: jax.Array | None = None,
    shift: jax.Array | None = None,
    alpha: jax.Array | None = None,
    residual: jax.Array | None = None,
    th: int | None = None,
    tc: int | None = None,
    compute_dtype=None,
    phase_sharding=None,
) -> jax.Array:
    """General 2-D convolution with the paper's decomposition applied.

    Args:
      x: (N, H, W, Cin) input.
      w: (kh, kw, Cin, Cout) compact kernel (never zero-inserted by the
        caller); rectangular ``kh != kw`` supported for plain dense convs.
      stride: forward-conv stride, or upsampling factor when ``transposed``.
      dilation: dilation step ``d = D + 1`` (forward conv only).
      transposed: run a transposed (fractionally-strided) convolution.
      padding: ``None`` -> SAME for forward conv, ``(k-1)//2`` for transposed.
      output_padding: transposed-conv extra size on the high side.
      decomposed: apply the paper's decomposition (False -> naive zero-laden
        execution, used as the measured baseline).
      strategy: 'batched' (TPU phase-batched) or 'ragged' (paper-faithful) for
        the dilated path.
      backend: 'xla' (composable lax convolutions) or 'pallas' (fused kernels
        from :mod:`repro.kernels`).
      interpret: Pallas interpret-mode override (None -> auto-detect; only
        meaningful with ``backend='pallas'``).
      epilogue: optional fused BN/PReLU/residual epilogue spec (DESIGN.md §7)
        with matching ``scale``/``shift``/``alpha``/``residual`` operands;
        fused in-kernel on pallas, applied as the reference oracle on xla.
      th, tc: Pallas tile shape override; ``None`` resolves through the
        autotune table (:mod:`repro.kernels.autotune`).
      compute_dtype: mixed-precision opt-in (DESIGN.md §12): ``None`` keeps
        the input dtype; a dtype (or alias string like ``"bf16"``) casts
        ``x``/``w``/``residual`` to it before dispatch, and the output comes
        back in it — accumulation stays fp32 inside the Pallas kernels, and
        the epilogue's channel operands (scale/shift/alpha) stay fp32
        throughout.  ``bf16`` in -> ``bf16`` out holds on every path.
      phase_sharding: optional hashable ``NamedSharding`` constraining the
        decomposition's phase/parity layout on a mesh (DESIGN.md §13) — the
        folded phase-batch of the dilated path, the per-parity-plane batch of
        the transposed path.  XLA decomposed paths only; usually set through
        :func:`repro.distributed.sharding.shard_conv2d` rather than directly.
    """
    if backend not in ("xla", "pallas"):
        raise ValueError(f"unknown backend {backend!r}")
    cd = canon_dtype(compute_dtype)
    if cd is not None:
        x = x.astype(cd)
        w = w.astype(cd)
        if residual is not None:
            residual = residual.astype(cd)
    if backend == "pallas" and not decomposed:
        # the fused kernels ARE the decomposition; the naive zero-laden
        # baseline only exists as composed XLA convolutions
        raise ValueError("naive execution has no pallas kernel; use backend='xla'")
    spec = EpilogueSpec() if epilogue is None else epilogue
    eps = pack_args(spec, scale=scale, shift=shift, alpha=alpha,
                    residual=residual)
    ep_kw = dict(zip(spec.slots, eps))
    engine = (obs.ENGINE_TRANSPOSED if transposed else
              obs.ENGINE_DILATED if dilation > 1 else obs.ENGINE_DENSE)
    with jax.named_scope(engine):
        kh, kw = w.shape[0], w.shape[1]
        if transposed:
            if dilation != 1:
                raise ValueError("dilated transposed convolution is not supported")
            if kh != kw:
                raise ValueError("transposed convolution requires square kernels")
            p = (kh - 1) // 2 if padding is None else padding
            if backend == "pallas":
                from repro.kernels.transposed_conv import transposed_conv2d as _ktr

                th, tc = _resolve_tiles("tconv", x, w, stride, 1, th, tc,
                                        padding=p, output_padding=output_padding,
                                        epilogue=spec)
                return _ktr(x, w, stride=stride, padding=p,
                            output_padding=output_padding, th=th, tc=tc,
                            interpret=interpret, epilogue=epilogue, **ep_kw)
            if decomposed:
                y = _tr.transposed_conv2d_decomposed(
                    x, w, stride, p, output_padding,
                    phase_sharding=phase_sharding)
            else:
                y = _tr.transposed_conv2d_naive(x, w, stride, p, output_padding)
            return apply_reference(spec, y, eps)
        if dilation > 1:
            if kh != kw:
                raise ValueError("dilated convolution requires square kernels")
            if backend == "pallas":
                if strategy != "batched":
                    raise ValueError(
                        f"pallas dilated path is phase-batched only, got {strategy!r}")
                from repro.kernels.dilated_conv import dilated_conv2d as _kdil

                th, tc = _resolve_tiles("dilated", x, w, stride, dilation, th, tc,
                                        epilogue=spec)
                return _kdil(x, w, dilation, stride=stride, th=th, tc=tc,
                             interpret=interpret, epilogue=epilogue, **ep_kw)
            if decomposed:
                y = _dil.dilated_conv2d_decomposed(
                    x, w, dilation, strategy=strategy, stride=stride,
                    phase_sharding=phase_sharding)
            else:
                y = _dil.dilated_conv2d_naive(x, w, dilation, stride=stride)
            return apply_reference(spec, y, eps)
        # plain dense conv (stride >= 1, rectangular kernels welcome)
        if backend == "pallas":
            from repro.kernels.conv2d import conv2d as _kconv

            th, tc = _resolve_tiles("dense", x, w, stride, 1, th, tc,
                                    padding=padding, epilogue=spec)
            return _kconv(x, w, stride=stride,
                          padding="SAME" if padding is None else padding,
                          th=th, tc=tc, interpret=interpret, epilogue=epilogue,
                          **ep_kw)
        from jax import lax

        if padding is None:     # SAME, asymmetric for even extents
            pads = [((kh - 1) // 2, kh // 2), ((kw - 1) // 2, kw // 2)]
        else:
            pads = [(padding, padding), (padding, padding)]
        y = lax.conv_general_dilated(
            x, w, window_strides=(stride, stride), padding=pads,
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
        )
        return apply_reference(spec, y, eps)


__all__ = ["conv2d"]
