"""ResNets: by default the paper's validation network, a ResNet-type CNN
with 21 conv layers for 32×32×3 / 10-class classification (He et al.
CIFAR ResNet-20 + two 1×1 projection shortcuts = 21 convs, ≈0.046
GOP/image as in paper §IV-B); :class:`ResNetConfig` also describes the
ImageNet form (7×7/2 stem and max-pool, bottleneck blocks: ResNet-50
v1.5). :func:`topology` is the one description every walk reads.

Pure-functional JAX: params/state are nested dicts, conv weights in HWIO
layout (kx, ky, cin, cout) matching ``core.groups.fpga_conv_groups``.
Supports quantization-aware training with the paper's Q2.5 (weights) /
Q3.4 (activations) fixed-point formats, and mask trees from any pruning
method in :mod:`repro.core`.
"""
from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core import quant as Q
from ..core.groups import fpga_conv_groups
from ..accel.cycle_model import ConvLayerDims
from ..kernels.ops import named_jit
from ..spans import NO_SPANS

PyTree = Any


class BindError(RuntimeError):
    """Base of the bind-failure taxonomy: anything that stops
    :func:`bind_execution` from producing a usable exec. The serving
    resilience ladder (:mod:`repro.launch.resilience`) keys its recovery
    on the subclass — transient failures retry with backoff, permanent
    ones downgrade immediately."""


class TransientBindError(BindError):
    """A bind failure that may succeed on retry (resource pressure,
    injected chaos, a racing invalidation) — the ladder retries it with
    exponential backoff before downgrading."""


class PermanentBindError(BindError, ValueError):
    """A bind failure no retry can fix: the request violates the bind
    contract (tracer weights, incompatible quant spec, ...). Also a
    :class:`ValueError` so pre-taxonomy callers catching that keep
    working. The ladder skips retries and downgrades one rung."""


@dataclasses.dataclass(frozen=True)
class ResNetConfig:
    stages: Tuple[int, ...] = (3, 3, 3)
    widths: Tuple[int, ...] = (16, 32, 64)
    num_classes: int = 10
    in_channels: int = 3
    image_size: int = 32
    quantized: bool = False            # QAT with Q2.5 / Q3.4
    bn_momentum: float = 0.9
    bn_eps: float = 1e-5
    block: str = "basic"               # "basic" or "bottleneck" (see topology)
    stem: str = "cifar"                # "cifar" or "imagenet" (see topology)

    def __post_init__(self):
        if self.block not in EXPANSION or self.stem not in STEMS:
            raise ValueError(f"unknown block {self.block!r} or stem "
                             f"{self.stem!r}; known: {sorted(EXPANSION)}, "
                             f"{sorted(STEMS)}")


# output channels of a block per unit of its stage width
EXPANSION = {"basic": 1, "bottleneck": 4}
# stem conv (kernel, stride), and whether a 3x3/2 max-pool follows it
STEMS = {"cifar": (3, 1, False), "imagenet": (7, 2, True)}


class ConvSite(NamedTuple):
    """One conv of the network: the param path of its node (``("conv0",)``,
    ``("s1b0", "conv2")``), the key of its BatchNorm beside it, kernel
    size, stride, channels, input size, and whether a ReLU follows its
    BatchNorm."""
    path: Tuple[str, ...]
    bn: str
    k: int
    stride: int
    cin: int
    cout: int
    in_size: int
    relu: bool

    @property
    def out_size(self) -> int:
        return -(-self.in_size // self.stride)       # SAME padding


class Block(NamedTuple):
    """A residual block ``relu(main(h) + shortcut(h))``: the main branch's
    convs in order, and the projection shortcut (``None``: identity)."""
    name: str
    convs: Tuple[ConvSite, ...]
    proj: Optional[ConvSite]


def topology(cfg: ResNetConfig) -> Tuple[ConvSite, Tuple[Block, ...]]:
    """The network's one description: the stem conv and the residual
    blocks in execution order (the stem's max-pool, where ``cfg.stem``
    has one, runs between them). A stage's first block strides by 2
    from stage 1 on. A basic block is 3x3 -> 3x3 at the stage width,
    stride on its first conv; a bottleneck block is 1x1 -> 3x3 -> 1x1
    with the last conv at 4x the stage width, stride on the 3x3 (the
    "v1.5" placement of MLPerf's and torchvision's ResNet-50). A block
    projects its shortcut (1x1 at the block's stride) where the stride
    or the width changes."""
    k, stride, pool = STEMS[cfg.stem]
    stem = ConvSite(("conv0",), "bn0", k, stride, cfg.in_channels,
                    cfg.widths[0], cfg.image_size, True)
    feat = -(-stem.out_size // 2) if pool else stem.out_size
    cin, blocks = cfg.widths[0], []
    for si, (n_blocks, width) in enumerate(zip(cfg.stages, cfg.widths)):
        cout = width * EXPANSION[cfg.block]
        for bi in range(n_blocks):
            name = f"s{si}b{bi}"
            stride = 2 if (si > 0 and bi == 0) else 1
            out = -(-feat // stride)

            def site(i, k, s, ci, co, size, relu):
                return ConvSite((name, f"conv{i}"), f"bn{i}", k, s, ci, co,
                                size, relu)
            if cfg.block == "bottleneck":
                convs = (site(1, 1, 1, cin, width, feat, True),
                         site(2, 3, stride, width, width, feat, True),
                         site(3, 1, 1, width, cout, out, False))
            else:
                convs = (site(1, 3, stride, cin, width, feat, True),
                         site(2, 3, 1, width, width, out, False))
            proj = (ConvSite((name, "proj"), "bnp", 1, stride, cin, cout, feat,
                             False) if stride != 1 or cin != cout else None)
            blocks.append(Block(name, convs, proj))
            feat, cin = out, cout
    return stem, tuple(blocks)


def conv_sites(cfg: ResNetConfig):
    """Every conv of :func:`topology`, in execution order (a block's main
    branch, then its projection)."""
    stem, blocks = topology(cfg)
    return [stem] + [c for b in blocks
                     for c in b.convs + ((b.proj,) if b.proj else ())]


def _node(tree: dict, path: Tuple[str, ...], make: bool = False) -> dict:
    """The dict that holds the conv at ``path`` (and its BatchNorm): the
    tree itself for the stem, the block's dict otherwise."""
    if len(path) == 1:
        return tree
    return tree.setdefault(path[0], {}) if make else tree[path[0]]


def _max_pool(h):
    """The ImageNet stem's 3x3 stride-2 max-pool, SAME-padded with the max's
    identity. It follows a ReLU, so that equals padding with zeros."""
    init = (-jnp.inf if jnp.issubdtype(h.dtype, jnp.floating)
            else jnp.iinfo(h.dtype).min)
    return jax.lax.reduce_window(h, jnp.array(init, h.dtype), jax.lax.max,
                                 (1, 3, 3, 1), (1, 2, 2, 1), "SAME")


# under a named jit: the compiled reduce-window's op_name reads jit(stem_pool)
stem_pool = named_jit(_max_pool, "stem_pool")


def _conv_init(key, kx, ky, cin, cout):
    fan_in = kx * ky * cin
    return jax.random.normal(key, (kx, ky, cin, cout)) * np.sqrt(2.0 / fan_in)


def _bn_init(c):
    return {"scale": jnp.ones((c,)), "bias": jnp.zeros((c,))}


def _bn_state_init(c):
    return {"mean": jnp.zeros((c,)), "var": jnp.ones((c,))}


def init(key: jax.Array, cfg: ResNetConfig) -> Tuple[PyTree, PyTree]:
    """Returns (params, state). state holds BN running stats."""
    # one key per conv and one for the fc head — a fixed split count would
    # StopIteration on deep configs (and waste keys on small ones). NOTE:
    # jax.random.split(key, n)[i] depends on n, so resizing the split
    # intentionally re-seeds all init streams per config.
    sites = conv_sites(cfg)
    keys = iter(jax.random.split(key, len(sites) + 1))
    params: dict = {}
    state: dict = {}
    for c in sites:
        p, s = _node(params, c.path, True), _node(state, c.path, True)
        p[c.path[-1]] = {"w": _conv_init(next(keys), c.k, c.k, c.cin, c.cout)}
        p[c.bn], s[c.bn] = _bn_init(c.cout), _bn_state_init(c.cout)
    cin = c.cout
    params["fc"] = {
        "w": jax.random.normal(next(keys), (cin, cfg.num_classes)) * np.sqrt(1.0 / cin),
        "b": jnp.zeros((cfg.num_classes,)),
    }
    return params, state


def _maybe_qw(w, cfg: ResNetConfig):
    return Q.quantize(w, Q.Q2_5) if cfg.quantized else w


def _maybe_qa(x, cfg: ResNetConfig):
    return Q.quantize(x, Q.Q3_4) if cfg.quantized else x


def _conv(x, w, stride):
    return jax.lax.conv_general_dilated(
        x, w, window_strides=(stride, stride), padding="SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )


def _logits(pooled, fc):
    """The classifier head at full f32 precision: XLA's default on a TPU
    rounds both operands to bf16, which would put the f32 execution
    contract 1e-3 off the f32 reference in the last layer alone."""
    return (jnp.dot(pooled, fc["w"], precision=jax.lax.Precision.HIGHEST)
            + fc["b"])


def _bn(x, p, s, train: bool, cfg: ResNetConfig):
    if train:
        mean = jnp.mean(x, axis=(0, 1, 2))
        var = jnp.var(x, axis=(0, 1, 2))
        new_s = {
            "mean": cfg.bn_momentum * s["mean"] + (1 - cfg.bn_momentum) * mean,
            "var": cfg.bn_momentum * s["var"] + (1 - cfg.bn_momentum) * var,
        }
    else:
        mean, var = s["mean"], s["var"]
        new_s = s
    y = (x - mean) * jax.lax.rsqrt(var + cfg.bn_eps) * p["scale"] + p["bias"]
    return y, new_s


def apply(
    params: PyTree,
    state: PyTree,
    x: jnp.ndarray,
    cfg: ResNetConfig,
    train: bool = False,
    *,
    sparse: Optional[SparseConvExec] = None,
) -> Tuple[jnp.ndarray, PyTree]:
    """Forward pass. ``x``: (B, H, W, C) in [0, 1]. Returns (logits, new_state).

    Pruning masks are applied to *params* beforehand (``core.apply_masks``),
    keeping this function mask-agnostic.

    ``sparse`` selects the conv execution path:
      - ``None``: dense ``lax.conv`` (default);
      - a :class:`SparseConvExec` (from :func:`bind_execution`):
        every conv dispatches through the Pallas block-sparse kernel on its
        bound plan with its *bind-time prepacked* weight (interpret mode on
        CPU, compiled on TPU), except layers the builder left dense
        (density ≈ 1 fallback). Build with ``quantized=cfg.quantized`` so
        the prepacked weights match the dense path's per-call quantization.

    A *prepacked* exec (the default bind) is inference-only with respect
    to the conv weights: bind-time prepacking makes them compile-time
    constants, so gradients could not reach ``params`` through sparse-bound
    layers — ``train=True`` with such an exec raises. An
    ``ExecSpec(trainable=True)`` bind instead passes each layer's (traced)
    weight to its bound conv per call, whose ``custom_vjp`` runs the
    transposed-plan / live-tile backward kernels: ``train=True`` is
    supported, gradients flow, pruned groups get exactly zero gradient.
    Rebind after each HAPM epoch either way.
    """
    sparse = _resolve_sparse(sparse, params, cfg.quantized)
    if train and sparse is not None and not sparse.trainable:
        raise ValueError(
            "this sparse exec is inference-only: conv weights are prepacked "
            "bind-time constants, so training gradients would silently not "
            "reach params — bind with ExecSpec(trainable=True) to train "
            "through the block-sparse kernels (rebind after each HAPM "
            "epoch), or train dense")

    def conv(path, h, w, stride):
        if sparse is not None:
            fn = sparse.table.get(path)
            if fn is not None:
                if sparse.trainable:
                    return fn(h, w, stride=stride)   # per-call (traced) weight
                return fn(h, stride=stride)   # weight prepacked at bind time
        return _conv(h, w, stride)

    def conv_bn(c, h, ns):
        p, s = _node(params, c.path), _node(state, c.path)
        y = conv(c.path + ("w",), h, _maybe_qw(p[c.path[-1]]["w"], cfg),
                 c.stride)
        y, ns[c.bn] = _bn(y, p[c.bn], s[c.bn], train, cfg)
        return _maybe_qa(jax.nn.relu(y), cfg) if c.relu else y

    new_state: dict = {}
    stem, blocks = topology(cfg)
    # the accelerator ingests Q3.4 activations for every layer, the input
    # frame included — quantize it so the executed-int8 path can match the
    # QAT forward exactly on codes (images are 8-bit sources anyway)
    h = conv_bn(stem, _maybe_qa(x, cfg), new_state)
    if STEMS[cfg.stem][2]:
        h = stem_pool(h)
    for blk in blocks:
        ns: dict = {}
        y = h
        for c in blk.convs:
            y = conv_bn(c, y, ns)
        sc = h if blk.proj is None else conv_bn(blk.proj, h, ns)
        h = _maybe_qa(jax.nn.relu(y + sc), cfg)
        new_state[blk.name] = ns
    return _logits(jnp.mean(h, axis=(1, 2)), params["fc"]), new_state


# ---------------------------------------------------------------------------
# Pruning / accelerator integration
# ---------------------------------------------------------------------------

def is_conv_weight(path, leaf) -> bool:
    """Prunable = 4-D conv kernels (the paper prunes conv layers)."""
    return hasattr(leaf, "ndim") and leaf.ndim == 4


def conv_group_specs(params: PyTree, n_cu: int) -> PyTree:
    """GroupSpec tree for HAPM over every conv weight (None elsewhere)."""
    def f(path, leaf):
        if is_conv_weight(path, leaf):
            return fpga_conv_groups(leaf.shape, n_cu)
        return None
    return jax.tree_util.tree_map_with_path(f, params)


def _get_path(tree, keys):
    node = tree
    for k in keys:
        node = node[k]
    return node


@dataclasses.dataclass(frozen=True)
class ExecSpec:
    """The execution contract of one bind: every knob that changes the
    compiled artifact :func:`bind_execution` produces. Frozen and hashable
    on purpose — it doubles as the spec component of the serving exec-cache
    key (``launch.exec_cache``: ``(arch fp, sparsity fp, spec, bucket)``),
    so two binds compare equal iff they are interchangeable.

    ``quantized``: native int8 Q2.5×Q3.4 execution (per-cout
    calibrated scales when ``folded``). ``folded``: the tree is
    ``fold_batchnorm`` output and the bias/ReLU epilogue is fused at the
    kernel flush (consume with :func:`apply_folded`). ``implicit``: the
    in-kernel window-gather data-movement contract (``None`` = auto).
    ``bm``: M-blocking policy, ``"auto"`` or a
    fixed int. ``n_cu``: the schedule-group granularity. Layers whose plan
    density reaches ``dense_fallback`` stay on dense ``lax.conv``.

    ``trainable``: bound convs take the caller's (traced) weight per call
    and carry a ``custom_vjp`` — :func:`apply` with ``train=True`` runs
    the block-sparse kernels forward *and* backward, gradients reach
    ``params``, pruned groups get exactly zero gradient. Incompatible with
    ``quantized``/``folded`` (both are inference contracts; QAT trains
    through the f32 fake-quant view, which this path consumes as-is).
    Rebind after each HAPM epoch, exactly like inference binds.

    ``streamed``: end-to-end int8 activation streaming — every bound
    conv's flush **requantizes in-epilogue** and emits int8 Q3.4 codes,
    which the next layer's gather consumes directly (the wire between
    layers carries 1-byte codes, no f32 round-trip through HBM — the
    paper's accelerator contract). Requires ``quantized`` (the wire is
    int8 codes) **and** ``folded`` (conv → +b → ReLU must complete
    in-kernel for the flushed value to be the final activation);
    inference-only. Consume with :func:`apply_folded`, which runs the
    whole residual dataflow on codes (int32 residual adds) and
    dequantizes once at the head.

    ``activation_dsb``: dual-sided sparsity — every bound implicit-kernel
    conv skips the gather+MXU pass for activation window blocks that are
    all-zero **int8 codes** (post-ReLU zeros are exact codes, so the
    skip is bit-exact at every density; Zhu et al., arXiv 2001.01955).
    Requires ``quantized`` (the zero test is exact only on codes) and the
    implicit kernel (``implicit`` must not be ``False``). Measure the
    realized skip with :meth:`SparseConvExec.measure_dsb_skip` /
    ``report(dsb_sample=...)``.

    Invalid field combinations raise a single :class:`ValueError` listing
    every violated pair by name — the contract table below is the one
    authority, callers never see layer-dependent messages.
    """

    quantized: bool = False
    folded: bool = False
    implicit: Optional[bool] = None
    bm: Any = "auto"
    n_cu: int = 12
    dense_fallback: float = 0.999
    trainable: bool = False
    streamed: bool = False
    activation_dsb: bool = False

    def __post_init__(self):
        # contract table: collect EVERY violation, raise once, naming the
        # offending fields — not first-failure-wins across layers
        violations = []
        if self.bm != "auto" and not isinstance(self.bm, int):
            violations.append(f"bm must be 'auto' or an int, got {self.bm!r}")
        if self.n_cu < 1:
            violations.append(f"n_cu must be >= 1, got {self.n_cu}")
        if self.trainable and self.quantized:
            violations.append(
                "trainable+quantized: int8-code execution is "
                "inference-only (QAT trains through the fake-quant f32 "
                "view; rebind quantized for serving)")
        if self.trainable and self.folded:
            violations.append(
                "trainable+folded: the fused bias/ReLU epilogue is "
                "inference-only (fold_batchnorm at serving bind time)")
        if self.trainable and self.streamed:
            violations.append(
                "trainable+streamed: activation streaming is "
                "inference-only (the requantizing epilogue has no VJP)")
        if self.streamed and not self.quantized:
            violations.append(
                "streamed without quantized: the wire between layers "
                "carries int8 Q3.4 codes — streaming requires the "
                "int8-code kernels")
        if self.streamed and not self.folded:
            violations.append(
                "streamed without folded: conv → +b → ReLU must complete "
                "in-kernel for the flush to emit the final activation "
                "codes — stream a fold_batchnorm tree")
        if self.activation_dsb and not self.quantized:
            violations.append(
                "activation_dsb without quantized: the zero-block skip is "
                "keyed on exact int8 codes — f32 zeros are a tolerance "
                "question the kernel refuses to answer")
        if self.activation_dsb and self.implicit is False:
            violations.append(
                "activation_dsb with implicit=False: the skip lives in "
                "the implicit kernel's window gather — the materializing "
                "path has no window to test")
        if violations:
            raise ValueError(
                "invalid ExecSpec: " + "; ".join(violations))


@dataclasses.dataclass(frozen=True)
class SparseConvExec:
    """Static dispatch table for the group-sparse conv path: conv param path
    -> bound block-sparse conv (``sparse.conv_plan.make_sparse_conv``, the
    masked weight prepacked at bind time), or ``None`` for layers left on
    the dense ``lax.conv`` fallback. ``plans`` keeps every layer's
    BlockSparsePlan (fallback layers included) for grid-step accounting;
    ``layouts`` / ``group_masks`` carry the occupancy-based schedule-group
    accounting that survives multi-group (packed) tiles. Rebuild after HAPM
    prunes more groups."""

    table: Any                       # {path: conv fn | None}
    plans: Any                       # {path: BlockSparsePlan}
    n_cu: int
    layouts: Any = None              # {path: ConvGemmLayout}
    group_masks_np: Any = None       # {path: (num_groups,) float}
    quantized: bool = False          # int8-code operands, int32-accumulate kernels
    folded: bool = False             # bias/ReLU epilogue fused (apply_folded only)
    streamed: bool = False           # in-epilogue requantize: layers exchange
                                     # int8 Q3.4 codes (apply_folded wire mode)
    activation_dsb: bool = False     # dual-sided: implicit kernel skips
                                     # all-zero int8 activation windows
    trainable: bool = False          # convs take per-call weights, custom_vjp
    bound_weights: Any = None        # {path: source weight} — staleness check
    implicit: bool = False           # convs bound to the implicit-im2col kernel
    bm: Any = 128                    # M-blocking policy: int (fixed) or "auto"
    spec: Optional[ExecSpec] = None  # the requested bind contract, if built
                                     # through bind_execution

    def _accounting(self, bm=None, implicit=None, operand_bytes=None,
                    dtype_bytes: int = 4, out_bytes=None):
        """The single default-resolution point for every accounting query:
        ``None`` means "this exec's own policy" — ``bm`` resolves to the
        bind-time M-blocking, ``implicit`` to the bound data-movement
        contract, ``operand_bytes`` to 1 byte for a quantized (int8-code)
        exec and ``dtype_bytes`` otherwise, ``out_bytes`` to 1 byte for a
        streamed exec (the requantizing epilogue writes int8 codes) and
        ``dtype_bytes`` otherwise (the f32 output write)."""
        return (self.bm if bm is None else bm,
                self.implicit if implicit is None else implicit,
                ((1 if self.quantized else dtype_bytes)
                 if operand_bytes is None else operand_bytes),
                ((1 if self.streamed else dtype_bytes)
                 if out_bytes is None else out_bytes))

    def _m_blocks(self, path, stride: int, feat: int, batch: int, bm=None,
                  implicit=None):
        """(M-blocks, effective bm, images per block) of one conv layer's
        grid at ``batch``. An implicit layer's M-block is the one its
        kernel launches (``sparse.conv_plan.implicit_m_block``, the rule
        the bound conv routes by: a fold the VMEM budget reduces, and one
        image a block under ``activation_dsb``); any other layer blocks
        on the materializing path's flat rows."""
        from ..sparse.conv_plan import conv_m_blocks, implicit_m_block
        bm, implicit, ob, _ = self._accounting(bm, implicit)
        mbk = (implicit_m_block(self.layouts[path], feat, feat, stride,
                                "SAME", ob, bm, batch=batch,
                                activation_dsb=self.activation_dsb)
               if implicit else None)
        if mbk is not None:
            return batch // mbk.ipb * mbk.bpi, mbk.m_rows, mbk.ipb
        out = -(-feat // stride)
        return (*conv_m_blocks(out, out, batch, bm=bm), 1)

    def images_per_block(self, cfg: ResNetConfig, batch: int = 1) -> dict:
        """{layer path: whole images one M-block holds} at ``batch`` under
        this exec's own policy — the implicit kernel's fold, 1 where one
        image takes several blocks or the layer is not implicit."""
        return {"/".join(path): self._m_blocks(path, stride, feat, batch)[2]
                for path, stride, feat in conv_layer_order(cfg)}

    def step_counts(self, cfg: ResNetConfig, batch: int = 1, bm=None):
        """(executed, dense) dispatched grid steps over the whole network —
        what the Pallas grid actually visits on *this* exec's tile layout
        and M-blocking policy (``bm=None`` → the exec's own; pass an int
        for the fixed PR-3 blocking). Executed steps per layer =
        M-row-blocks × live tiles."""
        executed = dense = 0
        for path, stride, feat in conv_layer_order(cfg):
            plan = self.plans[path]
            mb = self._m_blocks(path, stride, feat, batch, bm)[0]
            executed += mb * int(plan.cnt.sum())
            dense += mb * plan.tiles[0] * plan.tiles[1]
        return executed, dense

    def bm_effective(self, cfg: ResNetConfig, batch: int = 1, bm=None,
                     implicit=None):
        """{layer-path: effective bm} under this exec's M-blocking policy
        (``bm``/``implicit`` override it, e.g. the canonical adaptive
        implicit contract regardless of the bind)."""
        return {"/".join(path):
                self._m_blocks(path, stride, feat, batch, bm, implicit)[1]
                for path, stride, feat in conv_layer_order(cfg)}

    def routes(self, cfg: ResNetConfig) -> dict:
        """{layer path: "implicit" | "materializing" | "dense"} — the
        route each conv runs at ``cfg``'s input sizes, as its bound conv
        chose it (``conv.m_block``); "dense" for ``lax.conv`` layers."""
        out = {}
        for path, stride, feat in conv_layer_order(cfg):
            fn = self.table[path]
            out[path] = ("dense" if fn is None else "implicit"
                         if fn.m_block(feat, feat, stride, "SAME") is not None
                         else "materializing")
        return out

    def hbm_bytes(self, cfg: ResNetConfig, batch: int = 1,
                  implicit: Any = None, bm=None, dtype_bytes: int = 4,
                  operand_bytes: Any = None, out_bytes: Any = None) -> int:
        """Analytic HBM bytes one forward moves through the conv layers
        (``sparse.conv_plan.conv_hbm_bytes`` summed over the network) —
        patch-matrix traffic for the materializing path, activation-slab
        streaming for the implicit one. Defaults resolve through
        :meth:`_accounting`: the exec's own contract, M-blocking, operand
        width (1 byte when quantized), and output-write width (1 byte
        when streamed — the requantizing epilogue emits codes)."""
        from ..sparse.conv_plan import conv_hbm_bytes
        bm, use_implicit, operand_bytes, out_bytes = self._accounting(
            bm, implicit, operand_bytes, dtype_bytes, out_bytes)
        total = 0
        for path, stride, feat in conv_layer_order(cfg):
            total += conv_hbm_bytes(
                self.layouts[path], self.group_masks_np[path], batch, feat,
                feat, stride, "SAME", implicit=use_implicit,
                bm=bm, dtype_bytes=dtype_bytes, operand_bytes=operand_bytes,
                out_bytes=out_bytes, activation_dsb=self.activation_dsb)
        return total

    def schedule_step_counts(self):
        """(live, total) paper-granularity (g, f_block) schedule steps over
        the network, from per-tile group occupancy — layout-independent, so
        it equals the cycle model's DSB step count even when packed tiles
        cover many groups."""
        live = total = 0
        for path, layout in self.layouts.items():
            occ_live, occ_total = layout.tile_occupancy(self.group_masks_np[path])
            live += int(occ_live.sum())
            total += int(occ_total.sum())
        return live, total

    def mac_utilization(self, cfg: ResNetConfig, batch: int = 1,
                        bm=None) -> float:
        """Network padded-MAC utilization: useful MACs (real output rows ×
        live weight elements) per dispatched MAC area (padded M-blocks ×
        dispatched tile area). M-padding-aware: a batch-1 4×4 tail padded
        to a fixed ``bm=128`` shows up as an 8× utilization hit here,
        which the adaptive (``bm="auto"``) policy removes. At exact
        M-multiples this reduces to the PR-3 (M-cancelling) metric."""
        num = den = 0.0
        for path, stride, feat in conv_layer_order(cfg):
            out = -(-feat // stride)
            mb, bm_eff, _ = self._m_blocks(path, stride, feat, batch, bm)
            live_elems, area = self.layouts[path].mac_accounting(
                self.group_masks_np[path])
            num += batch * out * out * live_elems
            den += mb * bm_eff * area
        return num / den if den else 0.0

    def measure_dsb_skip(self, tree: PyTree, x: jnp.ndarray,
                         cfg: ResNetConfig, state: PyTree = None) -> dict:
        """One forward with the kernel-side skip counter on, through the
        real network dataflow (``apply_folded`` for folded execs,
        ``apply`` otherwise — ``state`` required there), summing each
        bound layer's ``conv.skip_counts`` stats.  Returns
        ``{"dsb_skip_frac", "dsb_skipped_steps", "dsb_live_steps",
        "dsb_per_layer"}`` — the *measured* dual-sided skip fraction
        (skipped / dispatched live grid steps; 0.0 for a bind without
        ``activation_dsb``), the number the simulator prices next to its
        ``data_col_nonzero_frac`` prediction.  ``tree`` is the tree the
        exec was bound from (the folded tree for folded execs); the
        forward's outputs are bit-identical to the unmeasured one (the
        counter is a second kernel output, not a different kernel)."""
        if self.trainable:
            raise ValueError("measure_dsb_skip needs a prebound exec — "
                             "trainable binds have no packed weight to "
                             "run the counter against")
        totals = {"skipped": 0, "live": 0}
        per_layer: dict = {}

        def wrap(keys, fn):
            def wrapped(h, stride=1, padding="SAME"):
                y, st = fn.skip_counts(h, stride=stride, padding=padding)
                if st is not None:
                    totals["skipped"] += st["skipped_steps"]
                    totals["live"] += st["live_steps"]
                    agg = per_layer.setdefault(
                        "/".join(keys), {"skipped_steps": 0, "live_steps": 0})
                    agg["skipped_steps"] += st["skipped_steps"]
                    agg["live_steps"] += st["live_steps"]
                return y
            return wrapped

        shadow = dataclasses.replace(self, table={
            k: (wrap(k, fn) if fn is not None else None)
            for k, fn in self.table.items()})
        if self.folded:
            apply_folded(tree, x, cfg, sparse=shadow)
        else:
            if state is None:
                raise ValueError("measure_dsb_skip on a non-folded exec "
                                 "runs apply() — pass the BN state")
            apply(tree, state, x, cfg, sparse=shadow)
        return {
            "dsb_skip_frac": totals["skipped"] / max(totals["live"], 1),
            "dsb_skipped_steps": totals["skipped"],
            "dsb_live_steps": totals["live"],
            "dsb_per_layer": per_layer,
        }

    def report(self, cfg: ResNetConfig, batch: int = 1, *,
               dtype_bytes: int = 4, per_layer: bool = False,
               dsb_sample: Optional[jnp.ndarray] = None,
               dsb_tree: PyTree = None,
               dsb_state: PyTree = None) -> dict:
        """Every accounting field in one dict — the single artifact the
        simulator (``accel.simulator``), the benches and the serving driver
        (``launch.serve_cnn``) consume instead of each re-assembling the
        same step/HBM/utilization numbers from the individual methods.

        The ``hbm_bytes_{materialized,implicit}[_int8]`` fields price the
        two data-movement contracts at their *defining* M-blocking
        (materializing: fixed ``bm=128``, the PR-3 contract; implicit:
        adaptive ``bm="auto"``) and at f32 / int8 operand widths — they are
        properties of the plans, independent of which contract this exec
        happens to bind. ``hbm_bytes_streamed_int8`` is the end-to-end
        int8 contract on top of the implicit one: 1-byte operands AND
        1-byte output writes (the requantizing epilogue emits Q3.4 codes
        the next layer ingests). ``hbm_bytes`` and the grid-step fields
        describe the exec's *own* policy (own contract, own ``bm``, own
        operand/output widths). ``layers_{implicit,materializing,dense}``
        count the conv layers on each route (:meth:`routes`);
        ``images_per_block`` is each layer's fold at ``batch``
        (:meth:`images_per_block`), which the grid-step fields follow.
        ``per_layer=True`` adds the same fields, plus
        the layer's ``path``, its ``kernel`` size and ``k_row_fill`` (the
        share of its dispatched K-tile rows that hold a weight,
        :meth:`~repro.sparse.conv_plan.ConvGemmLayout.k_row_fill`), per
        conv layer (keys ``"/".join(path)``), which is what the simulator
        reports next to the cycle model.

        ``dsb_sample`` (with ``dsb_tree``, the tree this exec was bound
        from, and ``dsb_state`` for non-folded execs) additionally runs
        :meth:`measure_dsb_skip` on that input and merges its
        ``dsb_skip_frac`` / ``dsb_skipped_steps`` / ``dsb_live_steps``
        fields — the measured dual-sided skip accounting."""
        executed, dense = self.step_counts(cfg, batch=batch)
        live, total = self.schedule_step_counts()
        routes = self.routes(cfg)
        hbm = lambda imp, bm, ob, out=None: self.hbm_bytes(
            cfg, batch, implicit=imp, bm=bm, dtype_bytes=dtype_bytes,
            operand_bytes=ob, out_bytes=dtype_bytes if out is None else out)
        rep = {
            "batch": batch,
            "n_cu": self.n_cu,
            "quantized": self.quantized,
            "folded": self.folded,
            "streamed": self.streamed,
            "activation_dsb": self.activation_dsb,
            "implicit": self.implicit,
            "bm": self.bm,
            "executed_grid_steps": executed,
            "dense_grid_steps": dense,
            "grid_step_ratio": executed / max(dense, 1),
            "schedule_steps_live": live,
            "schedule_steps_total": total,
            "schedule_step_ratio": live / max(total, 1),
            "padded_mac_utilization": self.mac_utilization(cfg, batch=batch),
            "dense_fallback_layers": sum(v is None
                                         for v in self.table.values()),
            **{f"layers_{route}": sum(v == route for v in routes.values())
               for route in ("implicit", "materializing", "dense")},
            "bm_effective": self.bm_effective(cfg, batch=batch),
            "images_per_block": self.images_per_block(cfg, batch=batch),
            "hbm_bytes": self.hbm_bytes(cfg, batch, dtype_bytes=dtype_bytes),
            "hbm_bytes_materialized": hbm(False, 128, dtype_bytes),
            "hbm_bytes_implicit": hbm(True, "auto", dtype_bytes),
            "hbm_bytes_materialized_int8": hbm(False, 128, 1),
            "hbm_bytes_implicit_int8": hbm(True, "auto", 1),
            "hbm_bytes_streamed_int8": hbm(True, "auto", 1, 1),
        }
        rep["hbm_bytes_ratio"] = (rep["hbm_bytes_implicit"]
                                  / max(rep["hbm_bytes_materialized"], 1))
        if per_layer:
            rep["per_layer"] = self._per_layer_report(cfg, batch, dtype_bytes,
                                                      routes)
        if dsb_sample is not None:
            rep.update(self.measure_dsb_skip(dsb_tree, dsb_sample, cfg,
                                             state=dsb_state))
        return rep

    def _per_layer_report(self, cfg: ResNetConfig, batch: int,
                          dtype_bytes: int, routes: dict) -> dict:
        from ..sparse.conv_plan import conv_hbm_bytes
        out = {}
        for c in conv_sites(cfg):
            path, stride, feat = c.path + ("w",), c.stride, c.in_size
            plan = self.plans[path]
            mb, bm_eff, ipb = self._m_blocks(path, stride, feat, batch)
            hbm = lambda imp, bm, ob, out_b=None: conv_hbm_bytes(
                self.layouts[path], self.group_masks_np[path], batch, feat,
                feat, stride, "SAME", implicit=imp, bm=bm,
                dtype_bytes=dtype_bytes, operand_bytes=ob,
                out_bytes=dtype_bytes if out_b is None else out_b,
                activation_dsb=self.activation_dsb)
            out["/".join(path)] = {
                "path": routes[path],
                "executed": mb * int(plan.cnt.sum()),
                "dense": mb * plan.tiles[0] * plan.tiles[1],
                "bm_effective": bm_eff,
                "images_per_block": ipb,
                "kernel": c.k,
                "k_row_fill": self.layouts[path].k_row_fill(
                    self.group_masks_np[path]),
                "hbm_materialized": hbm(False, 128, dtype_bytes),
                "hbm_implicit": hbm(True, "auto", dtype_bytes),
                "hbm_materialized_int8": hbm(False, 128, 1),
                "hbm_implicit_int8": hbm(True, "auto", 1),
                "hbm_streamed_int8": hbm(True, "auto", 1, 1),
            }
        return out


def conv_kernel_name(keys: tuple) -> str:
    """The name of a bound conv's kernel in the compiled program and in a
    device trace, from its param path: ``("s0b1", "conv2", "w")`` ->
    ``conv_s0b1_conv2``."""
    return "conv_" + "_".join(keys[:-1])


def _bind_conv_layers(tree: PyTree, specs: PyTree, group_masks: PyTree,
                      n_cu: int, weight_of, bind_one,
                      spans=NO_SPANS):
    """The bind loop of :func:`bind_execution`, plain and folded: walk the
    conv weights of ``tree``, derive each layer's (spec, group mask,
    layout, plan), and let ``bind_one(keys, w, layout, gm, plan, leaf)``
    produce the table entry.
    ``weight_of(leaf)`` is the weight the mask derivation should score
    (e.g. the Q2.5-quantized view); ``leaf`` is the raw array for binders
    that quantize themselves (a calibrated QuantSpec must see unclipped
    values — pre-quantizing onto the static grid would double-quantize).
    Each layer is one ``bind.layer.<kernel name>`` span of ``spans``."""
    from ..sparse.conv_plan import conv_gemm_layout

    if specs is None:
        specs = conv_group_specs(tree, n_cu)
    table, plans, layouts, gms, bound = {}, {}, {}, {}, {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        if not is_conv_weight(path, leaf):
            continue
        if isinstance(leaf, jax.core.Tracer):
            raise PermanentBindError(
                "sparse exec builders need concrete weights (plans are "
                "host-side numpy) but got a tracer — build the "
                "SparseConvExec outside jit and pass it via sparse=exec")
        keys = tuple(getattr(k, "key", str(k)) for k in path)
        with spans("bind.layer." + conv_kernel_name(keys)):
            w = weight_of(leaf)
            spec = _get_path(specs, keys)
            if group_masks is None:
                gm = None
            elif (isinstance(group_masks, dict)
                  and all(isinstance(k, tuple) for k in group_masks)):
                # flat {path-tuple: mask} form (exec.group_masks_np /
                # derive_group_masks) alongside the params-shaped pytree form
                gm = group_masks.get(keys)
            else:
                gm = _get_path(group_masks, keys)
            if gm is None:
                gm = np.asarray(spec.group_scores(w)) > 0
            gm = np.asarray(gm, np.float32)
            layout = conv_gemm_layout(spec)
            plan = layout.plan(gm)
            plans[keys], layouts[keys], gms[keys] = plan, layout, gm
            bound[keys] = leaf
            table[keys] = bind_one(keys, w, layout, gm, plan, leaf)
    return table, plans, layouts, gms, bound


def derive_group_masks(tree: PyTree, n_cu: int, *,
                       quantized: bool = False,
                       specs: PyTree = None) -> "dict[tuple, np.ndarray]":
    """The bind loop's default mask rule, standalone: per conv layer the
    {0,1} live-group mask from the weights' zero slabs
    (``group_scores(w) > 0``, scored on the Q2.5-quantized view when
    ``quantized`` — a group whose every value quantizes to zero is
    skippable in fixed-point execution even if not exactly zero in f32).
    Returned flat (``{path-tuple: mask}``), ready both for
    ``bind_execution(group_masks=...)`` and for
    :func:`repro.sparse.conv_plan.mask_fingerprint` — the serving cache
    fingerprints the sparsity pattern *without* paying a bind."""
    if specs is None:
        specs = conv_group_specs(tree, n_cu)
    weight_of = ((lambda l: Q.quantize(l, Q.Q2_5)) if quantized
                 else (lambda l: l))
    masks = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        if not is_conv_weight(path, leaf):
            continue
        keys = tuple(getattr(k, "key", str(k)) for k in path)
        w = weight_of(leaf)
        masks[keys] = np.asarray(
            np.asarray(_get_path(specs, keys).group_scores(w)) > 0,
            np.float32)
    return masks


def bind_execution(
    params: PyTree,
    cfg: Optional[ResNetConfig] = None,
    *,
    spec: Optional[ExecSpec] = None,
    specs: PyTree = None,
    group_masks: PyTree = None,
    quant_spec: Any = None,
    bind_kernels: bool = True,
    spans=NO_SPANS,
) -> SparseConvExec:
    """The one bind entry point: every conv layer of ``params`` onto the
    Pallas block-sparse kernels under the execution contract ``spec``
    (an :class:`ExecSpec`; default: auto-implicit kernel, adaptive
    M-blocking, f32).

    ``spec.folded=False`` (plain bind): ``params`` is the raw param tree.
    With ``spec.quantized`` every bound layer prepacks **int8 Q2.5 weight
    codes** (pruned groups stay zero codes) plus the per-cout dequant
    scale row, quantizes its input activation to int8 Q3.4 codes per
    call, and runs int8-operand / int32-accumulate kernels with the
    dequant fused at the flush — bit-exact vs a ``cfg.quantized`` dense
    forward. ``quant_spec`` overrides the static formats with a custom
    :class:`repro.core.quant.QuantSpec`. Consume with :func:`apply`.

    ``spec.folded=True``: ``params`` is ``fold_batchnorm`` output (per-conv
    ``{"w", "b"}``) and the bias — plus ReLU where the network applies it
    directly after BN (:func:`topology`; ``cfg`` is required) — is fused
    at the kernel's flush step. With ``spec.quantized`` each layer gets
    **per-cout calibrated** weight scales (BN folding scales channels
    arbitrarily, so the static Q2.5 grid would clip); ``quant_spec`` is
    rejected here.
    Consume with :func:`apply_folded`.

    ``spec.streamed=True`` (implies ``quantized`` + ``folded``): every
    bound layer's flush additionally **requantizes in-epilogue** to the
    uniform Q3.4 wire scale and emits int8 codes, and its ingest skips
    the per-call quantize when the input is already codes — chained
    conv→conv layers exchange 1-byte activations through HBM.
    :func:`apply_folded` detects the streamed exec and runs the whole
    residual dataflow on codes.

    ``cfg`` gives each conv's input size and stride: every bound layer's
    route (implicit kernel or materializing path) is then chosen once,
    here, and the exec serves only that input size. Without it (layer
    topology comes from the tree itself) each conv picks its route on
    its first call at each input size. ``specs``: GroupSpec tree (default:
    ``conv_group_specs(params, spec.n_cu)``). ``group_masks``:
    (num_groups,) {0,1} per conv leaf (e.g. ``HAPMState.group_masks``);
    ``None`` derives masks from the weights' zero slabs
    (``group_scores(w) > 0``, on the Q2.5-quantized view when
    ``spec.quantized``), matching the simulator's skippability rule.
    ``bind_kernels=False`` builds an **accounting-only** exec: plans,
    layouts and group masks for :meth:`SparseConvExec.report`, with every
    table entry ``None`` — no kernel closures, no weight packing (what
    ``accel.simulator`` prices).

    Each bound conv's kernel is named :func:`conv_kernel_name` of its
    path in the compiled program and in a device trace. ``spans`` (a
    :class:`repro.spans.Spans`; default none) records each layer's
    bind as a ``bind.layer.<kernel name>`` span.

    Host-side: requires concrete weights (plans are numpy; raises under
    jit — prebuild and pass the exec in); the bound kernels are jitted.
    The exec is pinned to these exact weight arrays — ``apply`` rejects a
    concrete params tree whose conv leaves differ (rebind after updates,
    or serve through ``launch.exec_cache`` which re-keys on the sparsity
    fingerprint).
    """
    from ..sparse.conv_plan import make_sparse_conv

    spec = ExecSpec() if spec is None else spec
    # each conv's site: with a config each layer's route is chosen once,
    # here, from its input size and stride
    sites = ({c.path + ("w",): c for c in conv_sites(cfg)}
             if cfg is not None else {})

    def bind_conv(keys, layout, gm, **kw):
        """One layer onto the kernels under ``spec``; a layer the bind
        contract cannot serve (e.g. ``activation_dsb`` at a geometry the
        implicit kernel does not fit) is a :class:`PermanentBindError`."""
        c = sites.get(keys)
        try:
            return make_sparse_conv(
                layout, gm, bm=spec.bm, implicit=spec.implicit,
                geometry=(None if c is None
                          else (c.in_size, c.in_size, c.stride, "SAME")),
                name=conv_kernel_name(keys), **kw)
        except ValueError as e:
            raise PermanentBindError(f"{'/'.join(keys)}: {e}") from e

    if spec.folded:
        if quant_spec is not None:
            raise PermanentBindError(
                "folded binds calibrate per-cout scales per layer — a "
                "global quant_spec would clip BN-scaled channels; it is "
                "plain-exec only")
        if cfg is None:
            raise PermanentBindError(
                "a folded bind fuses the ReLU that follows a conv, which "
                "the topology gives — pass cfg")
        tree = {k: v for k, v in params.items() if k != "fc"}
        weight_of = lambda l: l
        # streamed wire: every layer emits AND ingests the same static
        # Q3.4 activation scale (the per-layer chain is uniform — folded
        # binds calibrate weight scales only, activations stay on the
        # paper's fixed grid)
        out_q = Q.QuantSpec() if spec.streamed else None

        def bind_one(keys, w, layout, gm, plan, leaf):
            if not bind_kernels or plan.density >= spec.dense_fallback:
                return None
            bias = _get_path(params, keys[:-1])["b"]
            quant = Q.QuantSpec.calibrate(w) if spec.quantized else None
            if out_q is not None and quant.act_scale != out_q.act_scale:
                raise PermanentBindError(
                    f"streamed wire scale mismatch at {'/'.join(keys)}: "
                    f"layer ingests activation scale {quant.act_scale} but "
                    f"the wire emits {out_q.act_scale} — streaming needs a "
                    "uniform per-layer scale chain")
            return bind_conv(keys, layout, gm, weight=w, bias=bias,
                             relu=sites[keys].relu, quant=quant,
                             out_quant=out_q,
                             activation_dsb=spec.activation_dsb)
    else:
        if quant_spec is not None and not spec.quantized:
            raise PermanentBindError(
                "quant_spec without quantized=True would be "
                "silently ignored — pass quantized=True")
        qspec = (quant_spec or Q.QuantSpec()) if spec.quantized else None
        tree = params
        weight_of = ((lambda l: Q.quantize(l, Q.Q2_5)) if spec.quantized
                     else (lambda l: l))

        def bind_one(keys, w, layout, gm, plan, leaf):
            # quantized: bind the RAW weight — the quant spec emits the
            # codes itself, and a calibrated spec must not see values
            # pre-clipped to the static Q2.5 grid (for the static spec the
            # two are identical: round(fake_quant(w)·2^5) == round(w·2^5))
            if not bind_kernels or plan.density >= spec.dense_fallback:
                return None
            if spec.trainable:
                # no prepack: the conv re-packs the caller's (traced)
                # weight every call, so mid-epoch updates are never stale
                return bind_conv(keys, layout, gm, trainable=True)
            return bind_conv(keys, layout, gm,
                             weight=leaf if spec.quantized else w,
                             quant=qspec, activation_dsb=spec.activation_dsb)

    table, plans, layouts, gms, bound = _bind_conv_layers(
        tree, specs, group_masks, spec.n_cu, weight_of, bind_one, spans)
    return SparseConvExec(table=table, plans=plans, n_cu=spec.n_cu,
                          layouts=layouts, group_masks_np=gms,
                          quantized=spec.quantized, folded=spec.folded,
                          streamed=spec.streamed,
                          activation_dsb=spec.activation_dsb,
                          trainable=spec.trainable,
                          bound_weights=None if spec.trainable else bound,
                          implicit=spec.implicit is not False,
                          bm=spec.bm, spec=spec)


def _resolve_sparse(sparse, params, quantized: bool = False) -> Optional[SparseConvExec]:
    if sparse is None:
        return None
    if isinstance(sparse, SparseConvExec):
        if sparse.folded:
            raise ValueError(
                "this SparseConvExec fuses the folded-BN bias/ReLU epilogue "
                "(ExecSpec(folded=True)) — apply() would run BN on top of "
                "it; consume it with apply_folded()")
        if sparse.trainable:
            # per-call weights: nothing is prepacked, so there is nothing
            # to go stale and no code/float mismatch — under cfg.quantized
            # the f32 kernels consume the caller's fake-quant view (QAT)
            return sparse
        if sparse.quantized != quantized:
            raise ValueError(
                f"SparseConvExec prepacked with quantized={sparse.quantized} "
                f"but cfg.quantized={quantized} — rebind with "
                f"bind_execution(..., spec=ExecSpec(quantized={quantized}))")
        # staleness guard: the exec's convs compute with the weights packed
        # at bind time, so a concrete params tree with different conv leaves
        # would silently be ignored. (Tracers — the jitted path — can't be
        # identity-checked; the bind-time pin is documented there.)
        if sparse.bound_weights is not None:
            for keys, bound in sparse.bound_weights.items():
                try:
                    leaf = _get_path(params, keys[:-1])[keys[-1]]
                except (KeyError, TypeError):
                    leaf = None
                if (leaf is not bound and leaf is not None
                        and not isinstance(leaf, jax.core.Tracer)):
                    raise ValueError(
                        f"SparseConvExec is stale for {'/'.join(keys)}: its "
                        "prepacked bind-time weight is not the array in "
                        "params — rebuild the exec after weight updates")
        return sparse
    raise TypeError(f"sparse must be None or a SparseConvExec, got {type(sparse)}")


def conv_layer_order(cfg: ResNetConfig):
    """Execution-order list of (param-path, stride, input_feature_size) for
    every conv layer (21 for the default config)."""
    return [(c.path + ("w",), c.stride, c.in_size) for c in conv_sites(cfg)]


def layer_dims(cfg: ResNetConfig, params: PyTree):
    """ConvLayerDims (padded sizes) per conv layer, execution order —
    feeds the Eq.-3 cycle model."""
    dims = []
    for path, stride, feat in conv_layer_order(cfg):
        node = params
        for k in path:
            node = node[k]
        kx, ky, cin, cout = node.shape
        out = -(-feat // stride)           # SAME conv output
        padded = (out - 1) * stride + kx   # input size incl. padding (Alg. 1 note)
        dims.append((path, ConvLayerDims(
            n_ix=max(padded, feat), n_iy=max(padded, feat),
            n_if=cin, n_of=cout, kx=kx, ky=ky, sx=stride, sy=stride)))
    return dims


def network_ops(cfg: ResNetConfig, params: PyTree) -> int:
    return sum(d.ops for _, d in layer_dims(cfg, params))


def fold_batchnorm(params: PyTree, state: PyTree, cfg: ResNetConfig) -> PyTree:
    """Inference-time BN folding: w' = w·γ/√(σ²+ε) (per cout), b' = β − μ·γ/√(σ²+ε).

    Scaling per output channel preserves zero groups, so HAPM masks survive
    folding unchanged — this is what the accelerator executes.
    """
    folded = {}
    for c in conv_sites(cfg):
        p, s = _node(params, c.path), _node(state, c.path)
        g = p[c.bn]["scale"] * jax.lax.rsqrt(s[c.bn]["var"] + cfg.bn_eps)
        _node(folded, c.path, True)[c.path[-1]] = {
            "w": p[c.path[-1]]["w"] * g[None, None, None, :],
            "b": p[c.bn]["bias"] - s[c.bn]["mean"] * g}
    folded["fc"] = dict(params["fc"])
    return folded


def apply_folded(
    folded: PyTree,
    x: jnp.ndarray,
    cfg: ResNetConfig,
    *,
    sparse: Optional[SparseConvExec] = None,
    wire_quantize: Optional[bool] = None,
) -> jnp.ndarray:
    """Inference on BN-folded params (:func:`fold_batchnorm`): conv → +b →
    ReLU, no BN state. With ``sparse`` (a folded :class:`SparseConvExec`)
    every non-fallback conv runs through the block-sparse kernel with the
    bias/ReLU epilogue *fused at the flush step* — the accelerator's
    folded-BN execution, in one kernel per layer. Returns logits only.

    **Wire-quantized dataflow** (``ExecSpec(streamed=True)`` execs, or
    ``wire_quantize=True`` explicitly): every conv layer emits int8 Q3.4
    codes onto the wire — in-epilogue for streamed kernels, host-side
    ``round_sat`` at the identical program point otherwise — the first
    layer ingests the f32 frame, residual adds run on codes in exact
    int32 arithmetic (``clip(y + sc, 0, 127)`` *is*
    ``requantize(relu(dequant(y) + dequant(sc)))`` because Q3.4 codes
    dequantize exactly in f32), and the head dequantizes once before the
    average pool. ``wire_quantize=True`` on a **non-streamed** quantized
    folded exec is therefore the bit-exact reference for the streamed
    path: same kernels, same program points, requantization outside the
    kernel instead of inside — the bench gates their end-to-end code
    parity. The default float dataflow (f32 residual adds) is unchanged.
    """

    if sparse is not None and not sparse.folded:
        raise ValueError(
            "apply_folded needs a folded SparseConvExec (ExecSpec("
            "folded=True)) — this one has no fused bias/ReLU epilogue, its "
            "convs would silently drop the folded bias")
    streamed = sparse is not None and sparse.streamed
    if streamed and wire_quantize is False:
        raise ValueError(
            "this exec's kernels requantize in-epilogue (streamed=True) — "
            "the wire dataflow cannot be disabled; bind streamed=False "
            "for the f32-output folded path")
    if wire_quantize and sparse is not None and not sparse.quantized:
        raise ValueError(
            "wire_quantize puts int8 codes on the wire — the bound f32 "
            "kernels cannot ingest them; use a quantized folded exec "
            "(the streamed-parity reference) or sparse=None")
    wire = streamed or bool(wire_quantize)
    # Q3.4 wire: the uniform activation scale every layer emits/ingests
    wire_scale = float(Q.Q3_4.scale)
    max_code = float(Q.Q3_4.max_code)

    def requant(y):
        return Q.round_sat(y * wire_scale, max_code).astype(jnp.int8)

    def conv(path, h, stride, relu):
        fn = sparse.table.get(path) if sparse is not None else None
        if fn is not None:
            y = fn(h, stride=stride)      # bias/ReLU fused per the builder
            if not wire or y.dtype == jnp.int8:   # streamed: already codes
                return y
            return requant(y)             # wire reference: requantize here
        node = _get_path(folded, path[:-1])
        if h.dtype == jnp.int8:           # fallback layer on the wire:
            h = h.astype(jnp.float32) / wire_scale    # exact f32 dequant
        y = _conv(h, node["w"], stride) + node["b"]
        y = jax.nn.relu(y) if relu else y
        return requant(y) if wire else y

    def run(c, h):
        return conv(c.path + ("w",), h, c.stride, c.relu)

    stem, blocks = topology(cfg)
    h = run(stem, x)
    if STEMS[cfg.stem][2]:
        h = stem_pool(h)
    for blk in blocks:
        y = h
        for c in blk.convs:
            y = run(c, y)
        sc = h if blk.proj is None else run(blk.proj, h)
        if wire:
            # residual add + ReLU on codes: int32 widen, clamp to the
            # post-ReLU code range — exact integer arithmetic
            h = jnp.clip(y.astype(jnp.int32) + sc.astype(jnp.int32),
                         0, int(max_code)).astype(jnp.int8)
        else:
            h = jax.nn.relu(y + sc)
    if wire:
        h = h.astype(jnp.float32) / wire_scale        # head: exact dequant
    return _logits(jnp.mean(h, axis=(1, 2)), folded["fc"])
