"""CompiledLinear — the paper's technique as a first-class module.

Every parameterized linear map in every architecture (QKV/out projections,
FFN/SwiGLU, MoE experts, MLA projections, Mamba/RWKV projections, conv via
im2col, the LM head) routes through ``apply_linear``.  The weight leaf is,
per serving compilation mode:

  dense        raw bf16/f32 array                      (training / baseline)
  int8         {'values': int8, 'scale'}                W-INT7 A-INT8 QDQ,
               direct int8 MXU matmul (2x bf16 peak)
  cfmm         {'codes': int8, 'scale'}                 same storage; compute
               routed through the CFMM product-table / LUT-decode Pallas
               kernel (kernels/cfmm_matmul) — the paper's dataflow
  sparse_cfmm  {'bitmap': uint8, 'values': int8, 'scale'}
               bitmap-packed constant sparsity: (1-s)*8 + 1 bits/param
               (~2.6 bits at s=0.8 vs 16 for bf16) — the paper's
               zero-overhead sparsity converted to a memory-bandwidth win.
               K pads up to a multiple of 8 with masked all-zero rows
  bitserial    {'codes': int8, 'scale'}, bit-plane matmul — FPGA bit-serial
               ablation (sum_b 2^b * (x @ ternary plane_b))

EVERY conv leaf — packed or dense — is stored in the conv kernels'
spatial-major tap layout (row = tap*c_in + c, kernels/conv_sparse.py) at
compile time: serving streams the stored bytes straight into VMEM and
``ops.conv2d`` performs zero call-time layout shuffles; the single
permute (kernels.ref.to_spatial_major) runs here, once.

``compile_params`` converts a trained parameter tree into its constant-
parameter ("Compiled NN") serving form.  It is jax-traceable, so the
multi-pod dry-run builds packed serving params with jax.eval_shape — no
real weights are ever allocated.

Deviation from the paper (documented in DESIGN.md): pruning for
sparse_cfmm is per-output-channel balanced (top-k per column) rather than
globally unstructured, so the packed value buffer is rectangular with a
static shape.  Overall sparsity is identical; the FPGA needs no such
balance but a static-shape accelerator buffer does.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro import nn
from repro.core import cfmm
from repro.core.quantize import INT8_ACT_MAX, quantize_int7
from repro.kernels import ref as kref
from repro.kernels.bitmap import expand_bitmap_tile

SERVE_MODES = ("dense", "int8", "cfmm", "sparse_cfmm", "bitserial")


def act_quant(x: jax.Array, *, per_row: bool = False):
    """Dynamic INT8 activation quantization (the Collector saturates/
    rounds activations to 8 bits, paper SS II-D.4).

    ``per_row=False``: one tensor-wide scalar scale (the per-microbatch
    quantization domain).  ``per_row=True``: one scale per leading-axis
    row — scale shape ``(N,)`` for ``(N, ...)`` input — the per-image
    domain the compiled ResNet path serves under, so a row's int8 codes
    never depend on its batch neighbours and microbatches may pack rows
    from different requests (DESIGN.md §9).
    """
    axes = tuple(range(1, x.ndim)) if per_row else None
    amax = jnp.max(jnp.abs(x), axis=axes)
    scale = (jnp.maximum(amax, 1e-12) / INT8_ACT_MAX).astype(jnp.float32)
    s_b = scale.reshape((-1,) + (1,) * (x.ndim - 1)) if per_row else scale
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / s_b),
                 -INT8_ACT_MAX, INT8_ACT_MAX).astype(jnp.int8)
    return q, scale


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class ConvGeom:
    """Static (k, stride, c_in) geometry riding a compiled conv weight.

    A childless pytree node: it passes through nn.unbox / tree.map /
    eval_shape untouched, so compiled conv leaves stay self-describing —
    consumers never re-plumb filter size or stride alongside the weight.

    ``dw=True`` marks a depthwise leaf (groups == channels): storage is
    tap-major ``(k*k, C)`` and ``apply_conv`` routes it to the depthwise
    tap-MAC kernel (kernels/conv_depthwise.py) instead of implicit-GEMM;
    ``c_in`` is 1 (per-output-channel input fan-in), which also makes the
    analytic ``ConvLayerSpec`` MAC/param counts come out right.
    """

    k: int
    stride: int
    c_in: int
    dw: bool = False

    def tree_flatten(self):
        return (), (self.k, self.stride, self.c_in, self.dw)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*aux)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class KDim:
    """Static unpadded-K marker riding an off-%8 *linear* bitmap leaf.

    The pad_rows8 rule stores such leaves with ceil(K/8)*8 rows; this
    childless pytree node (same pattern as ConvGeom) records the original
    K so ``packed_codes``/``dense_of`` keep their shape contract for
    algebraic consumers.  Conv leaves need no marker — their ``geom``
    already determines K = k*k*c_in.
    """

    k: int

    def tree_flatten(self):
        return (), (self.k,)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*aux)


# ---------------------------------------------------------------------------
# Bitmap packing (traceable; shapes static given keep_k)
# ---------------------------------------------------------------------------

def balanced_prune_codes(w: jax.Array, keep_k: int) -> jax.Array:
    """Keep the top-``keep_k`` |w| entries per column; quantize to INT7."""
    ranks = jnp.argsort(jnp.argsort(-jnp.abs(w), axis=0, stable=True),
                        axis=0, stable=True)
    pruned = jnp.where(ranks < keep_k, w, 0.0)
    return quantize_int7(pruned, axis=-1)


def bitmap_pack(codes: jax.Array, keep_k: int):
    """int8 codes (K, N) with <= keep_k nonzeros/col -> (bitmap, values).

    bitmap: (K/8, N) uint8, little-endian bit j of row r = mask[8r+j].
    values: (keep_k, N) int8, nonzeros in ascending row order.
    """
    K, N = codes.shape
    assert K % 8 == 0, f"K={K} must be divisible by 8"
    mask = codes != 0
    pos = jnp.cumsum(mask, axis=0) - 1                      # rank within col
    pos = jnp.where(mask, pos, keep_k)                      # park drops
    cols = jnp.broadcast_to(jnp.arange(N)[None, :], (K, N))
    values = jnp.zeros((keep_k, N), jnp.int8)
    values = values.at[pos, cols].set(codes, mode="drop")
    bits = mask.reshape(K // 8, 8, N).astype(jnp.uint8)
    weights = (jnp.uint8(1) << jnp.arange(8, dtype=jnp.uint8))[None, :, None]
    bitmap = jnp.sum(bits * weights, axis=1).astype(jnp.uint8)
    return bitmap, values


def bitmap_unpack(bitmap: jax.Array, values: jax.Array) -> jax.Array:
    """Inverse of bitmap_pack -> dense int8 codes (K, N): one full-slab
    call of the kernels' shared expand tile (kernels/bitmap.py) — the
    format decode lives in exactly one place."""
    base = jnp.zeros((1, bitmap.shape[1]), jnp.int32)
    return expand_bitmap_tile(bitmap, values, base, values.shape[0])[0]


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def dense_of(w, dtype=jnp.float32) -> jax.Array:
    """Dequantize any weight-leaf form back to a dense array.

    Used by paths that consume the weight *algebraically* rather than as a
    plain matmul (e.g. MLA's absorbed decode pulls k_up through q).  Cheap:
    the decode is elementwise and the consumers are small projections.
    """
    if isinstance(w, nn.Param):
        w = w.value
    if not isinstance(w, dict):
        return w.astype(dtype)
    return packed_codes(w).astype(dtype) * w["scale"].astype(dtype)


def packed_codes(w: dict) -> jax.Array:
    """Dense int8 codes of any packed weight leaf (bitmap forms expand —
    the jnp analogue of the in-VMEM expansion the sparse kernel does).
    The single source of truth for the per-mode storage keys.

    EVERY conv leaf (a ``geom`` entry rides the dict) is stored in the
    kernels' spatial-major tap layout at compile time — bitmap leaves
    additionally K-padded to a multiple of 8 (kernels/conv_sparse.py);
    this strips the pad and permutes back to the channel-major patch
    order every other consumer speaks.  NOT on the serving hot path —
    ``apply_conv`` hands the stored bytes straight to the kernel."""
    geom = w.get("geom")
    if geom is not None and geom.dw:   # depthwise leaf: tap-major (k*k, C)
        return w["values"]             # storage IS the canonical layout
    if "bitmap" in w:
        dense = bitmap_unpack(w["bitmap"], w["values"])
        if geom is not None:           # conv leaf: spatial-major, K padded
            kk = geom.c_in * geom.k * geom.k
            dense = kref.from_spatial_major(dense[:kk], geom.k, geom.c_in)
        elif "kdim" in w:              # linear leaf: strip the K%8 pad
            dense = dense[:w["kdim"].k]
        return dense
    dense = w.get("codes", w.get("bs_codes", w.get("values")))
    if geom is not None:               # dense conv leaf: spatial-major
        dense = kref.from_spatial_major(dense, geom.k, geom.c_in)
    return dense


def _flatten_batch(x: jax.Array):
    lead = x.shape[:-1]
    return x.reshape((-1, x.shape[-1])), lead


def _int8_dot(x_q: jax.Array, w_int8: jax.Array) -> jax.Array:
    return jax.lax.dot_general(
        x_q, w_int8, dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)


def apply_linear(w, x: jax.Array, qat: bool = False,
                 per_row: bool = False) -> jax.Array:
    """y = x @ W for any compiled or dense weight leaf.  Preserves x.dtype.

    ``per_row=True`` quantizes each flattened input row under its own
    INT8 domain (scale per row of the (M, K) matmul input) instead of one
    tensor-wide scale — the compiled ResNet head uses this so a request's
    logits never depend on which rows share its microbatch (DESIGN.md §9).
    """
    if isinstance(w, nn.Param):
        w = w.value
    if not isinstance(w, dict):                    # dense (array / tracer)
        wv = w
        if qat:
            from repro.core.quantize import fake_quant_int7
            wv = fake_quant_int7(wv.astype(jnp.float32), axis=-1).astype(x.dtype)
        return jnp.matmul(x, wv.astype(x.dtype))

    # compiled conv leaves are stored spatial-major (bitmap ones also
    # K-padded) — silently wrong under a plain matmul; use apply_conv
    assert "geom" not in w, "compiled conv leaf: use apply_conv"
    x2, lead = _flatten_batch(x)
    x_q, s_x = act_quant(x2, per_row=per_row)
    if "bitmap" in w:                              # sparse_cfmm
        from repro.kernels import ops
        acc = ops.sparse_cfmm_matmul(x_q, w["bitmap"], w["values"])
    elif "bs_codes" in w:                          # bitserial ablation
        acc = cfmm.bitserial_matmul(x_q, w["bs_codes"])
    elif "codes" in w:                             # cfmm
        from repro.kernels import ops
        acc = ops.cfmm_matmul(x_q, w["codes"])
    else:                                          # int8
        acc = _int8_dot(x_q, w["values"])
    s_row = s_x.reshape(-1, 1) if per_row else s_x
    y = acc.astype(jnp.float32) * (s_row * w["scale"].reshape(1, -1))
    return y.reshape(lead + (y.shape[-1],)).astype(x.dtype)


def conv_codes_of(w: dict):
    """Dense *channel-major* int8 codes + per-channel scale of a compiled
    conv leaf.

    Oracle/debug seam only: every conv leaf is stored spatial-major at
    compile time (bitmap leaves additionally packed), and this un-permutes
    (and expands) through ``packed_codes``.  The serving path never calls
    it — ``apply_conv`` hands the stored bytes straight to the kernels.
    ``bs_codes`` (bit-serial ablation) are bit-exact equal to plain codes
    as int8 operands, so they ride the MXU path too — the bit-plane loop
    remains a linear-layer-only ablation.
    """
    return packed_codes(w), w["scale"]


def apply_conv(w: dict, x_q: jax.Array, x_scale, *, gamma=None, beta=None,
               shortcut=None, relu: bool = True, quant_out: bool = False,
               zero_count: int | None = None):
    """Fused conv forward for a compiled conv leaf (carries its geometry).

    x_q (N, H, W, c_in) int8 + its scalar scale; gamma/beta are the
    folded-BN scale and bias Collector vectors.  Returns f32 NHWC, or
    (int8, scale) with quant_out (see kernels.ops.conv2d).
    ``zero_count`` opts into activation-sparsity profiling: the zero-count
    aux dict is appended to the return, observation-only (DESIGN.md §11).

    Dispatch rides the leaf's storage keys: ``bitmap`` leaves hand the
    packed (bitmap, values) pair straight to the bitmap-native sparse conv
    kernel — no expansion at the op boundary, HBM sees ~2.6 bits/param at
    s=0.8 — everything else feeds the dense-codes implicit-GEMM kernel.
    All conv leaves are stored in the kernels' spatial-major tap layout at
    compile time, so NO layout shuffle happens here or in ``ops.conv2d``
    (spy-tested in tests/test_conv.py).
    """
    geom = w["geom"]
    from repro.kernels import ops
    if geom.dw:                        # depthwise: tap-MAC kernel
        return ops.conv2d_dw(x_q, w["values"], geom.k, geom.stride,
                             x_scale=x_scale, w_scale=w["scale"],
                             gamma=gamma, beta=beta, shortcut=shortcut,
                             relu=relu, quant_out=quant_out,
                             zero_count=zero_count)
    if "bitmap" in w:                  # sparse_cfmm: packed weights only
        codes = (w["bitmap"], w["values"])
    else:
        codes = w.get("values", w.get("codes", w.get("bs_codes")))
    return ops.conv2d(x_q, codes, geom.k, geom.stride, x_scale=x_scale,
                      w_scale=w["scale"], gamma=gamma, beta=beta,
                      shortcut=shortcut, relu=relu, quant_out=quant_out,
                      w_layout="spatial", zero_count=zero_count)


# ---------------------------------------------------------------------------
# Compilation (training tree -> constant-parameter serving tree)
# ---------------------------------------------------------------------------

def _compile_leaf(p: nn.Param, mode: str, sparsity: float):
    w = p.value.astype(jnp.float32)
    lead, in_ax, out_ax = p.axes[:-2], p.axes[-2], p.axes[-1]
    dw = nn.dwconv_geom_of(p.kind)
    if dw is not None:
        # Depthwise leaves store dense tap-major int8 values in EVERY
        # serve mode: K = k*k (9 for the 3x3 mobilenet case), so a bitmap
        # or LUT re-encoding of 9 rows saves nothing and would only add a
        # per-tap decode to the VPU inner loop — the weight-bytes win of
        # sparse_cfmm lives in the pointwise convs that dominate
        # mobilenet's parameters, and those pack normally.
        assert w.ndim == 2, f"stacked depthwise leaves unsupported: {w.shape}"
        k, stride = dw
        assert w.shape[0] == k * k, (w.shape, p.kind)
        qt = quantize_int7(w, axis=-1)             # per-channel scale
        return {"values": nn.Param(qt.values, (in_ax, out_ax)),
                "scale": nn.Param(qt.scale.reshape(1, -1), (None, out_ax)),
                "geom": ConvGeom(k, stride, 1, dw=True)}
    geom = nn.conv_geom_of(p.kind)
    conv_k = geom[0] if geom is not None else None
    fn = lambda wi: _compile_leaf_2d(wi, mode, sparsity, conv_k)
    for _ in range(w.ndim - 2):                    # stacked (layers/experts)
        fn = jax.vmap(fn)
    out = fn(w)
    packed = {k: nn.Param(v, _leaf_axes(k, lead, in_ax, out_ax))
              for k, v in out.items()}
    if geom is not None:                           # conv weights stay
        k, stride = geom                           # self-describing
        packed["geom"] = ConvGeom(k, stride, w.shape[-2] // (k * k))
    elif mode == "sparse_cfmm" and w.shape[-2] % 8 != 0:
        packed["kdim"] = KDim(w.shape[-2])         # unpadded K (pad_rows8)
    return packed


def _leaf_axes(kind: str, lead, in_ax, out_ax):
    if kind == "scale":
        return lead + (None, out_ax)
    if kind == "bitmap":
        return lead + (in_ax, out_ax)    # rows = ceil(in/8) (K padded to %8)
    if kind == "values":
        return lead + (None, out_ax)
    return lead + (in_ax, out_ax)        # codes / bs_codes


def pad_rows8(codes: jax.Array) -> jax.Array:
    """Pad the K axis up to a multiple of 8 with all-zero (masked) rows —
    the bitmap K-padding rule.  Zero codes pack to zero bits, so the pad
    is invisible to the sparse kernels and exact under int8 matmul."""
    pad = (-codes.shape[0]) % 8
    if pad == 0:
        return codes
    return jnp.pad(codes, ((0, pad), (0, 0)))


def _compile_leaf_2d(w: jax.Array, mode: str, sparsity: float,
                     conv_k: int | None = None) -> dict:
    K = w.shape[0]
    if mode == "sparse_cfmm":
        keep_k = max(8, int(round(K * (1.0 - sparsity))))
        keep_k = min(K, ((keep_k + 7) // 8) * 8)
        qt = balanced_prune_codes(w, keep_k)
        codes = qt.values
        if conv_k is not None:
            # conv leaves pack in the kernels' spatial-major tap layout
            # (row = tap*c_in + c) so the packed pair feeds
            # kernels/conv_sparse.py with no boundary permute/expand
            codes = kref.to_spatial_major(codes, conv_k,
                                          K // (conv_k * conv_k))
        # K % 8 != 0 (e.g. the 7x7 stem, K = 3*49 = 147): pad + mask
        # instead of the old silent dense fallback
        bitmap, values = bitmap_pack(pad_rows8(codes), keep_k)
        return {"bitmap": bitmap, "values": values,
                "scale": qt.scale.reshape(1, -1)}
    qt = quantize_int7(w, axis=-1)
    codes = qt.values
    if conv_k is not None:
        # dense conv leaves store spatial-major too: the one weight-layout
        # shuffle runs here, at compile time, and ops.conv2d streams the
        # stored bytes with zero call-time permutes (per-column scales are
        # row-permutation-invariant, so the codes permute is free)
        codes = kref.to_spatial_major(codes, conv_k, K // (conv_k * conv_k))
    key = {"int8": "values", "bitserial": "bs_codes"}.get(mode, "codes")
    return {key: codes, "scale": qt.scale.reshape(1, -1)}


def compile_params(params, mode: str = "sparse_cfmm", sparsity: float = 0.8):
    """Convert a trained param tree to its Compiled-NN serving form.

    Only linear- and conv-kind leaves are packed; norms, embeddings, biases
    and routers stay in their training dtype.  Compiled conv leaves gain a
    static ``geom`` (k, stride, c_in) entry so the serving path needs no
    side-channel geometry.  Traceable — safe under jax.eval_shape for the
    dry run.
    """
    assert mode in SERVE_MODES, mode
    if mode == "dense":
        return params

    def visit(p):
        if isinstance(p, nn.Param) and nn.compilable(p.kind) \
                and p.value.ndim >= 2:
            return _compile_leaf(p, mode, sparsity)
        return p

    return jax.tree.map(visit, params, is_leaf=lambda x: isinstance(x, nn.Param))


def ensure_compiled(params, mode: str, sparsity: float):
    """The serving engines' front door: a boxed training tree compiles
    (and unboxes) to its constant-parameter form; an already-compiled
    unboxed tree passes through UNTOUCHED — callers may rely on the
    identity (``out is params``) to share one host-side tree across
    engines (serving/frontend.py does).  A serve mode the chip cannot
    run is refused here, before any pruning or packing."""
    if mode == "sparse_cfmm":
        from repro.kernels import ops
        ops._refuse_bitmap_on_tpu(ops._mode())
    boxed = any(isinstance(l, nn.Param) for l in jax.tree.leaves(
        params, is_leaf=lambda x: isinstance(x, nn.Param)))
    return nn.unbox(compile_params(params, mode=mode, sparsity=sparsity)) \
        if boxed else params
