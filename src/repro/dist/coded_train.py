"""Coded training on a device mesh: the paper's update, for real.

The parameter-server view (Glasgow & Wootters, Algorithm 2) is

    theta <- theta - eta * sum_j w*_j g_j

over m coded workers, where g_j is worker j's sum of assigned block
gradients and w* comes from the O(m) optimal decoder applied to this
round's straggler mask. On the mesh, the m workers are the (pod, data)
shards: the coded batch carries a leading machine axis of size m (see
``data.pipeline.CodedBatcher``), the per-worker weighted loss

    L(theta) = (1/N) sum_j w_j sum_{l} block_weight_{jl} * L_{jl}(theta)

is *linear in w*, so its autodiff gradient IS the paper's combine
``sum_j w_j g_j`` -- the contract ``tests/test_dist.py`` pins against
the explicit ``coded_combine_tree``. Under ``jit`` the machine axis is
data-sharded and GSPMD inserts the psum; ``coded_allreduce`` is the
same combine as an explicit ``shard_map`` collective for runs that
want manual control over the reduction.

Four execution models, one algebra
----------------------------------

The module offers the paper's update in four equivalent forms; picking
between them is picking what the mesh is *simulating*:

* **Replicated-machine** (``coded_loss_fn``): the batch carries the
  (m, load, ...) machine axis with every block materialised d times,
  exactly as a real straggling cluster would compute it -- machine j
  really does redo block i's forward/backward. This is the right model
  when the mesh shards *are* the m unreliable workers (a real cluster,
  or fault-injection studies where per-machine compute matters).
* **Dedup-block** (``coded_loss_fn_dedup``): for a *reproduction* on a
  reliable mesh, the d-fold replication is a coding-layer fact, not a
  compute obligation. The combine ``sum_j w_j g_j`` is algebraically
  ``sum_i (A w)_i grad L_i`` over the n unique blocks (machine j's
  gradient is the sum of its blocks' gradients -- the same identity
  Charles et al. use to analyse the decoded gradient), so the step
  runs each block once, weighted by ``v = A @ w``
  (``core.step_weights.block_weights``), at ~1x the uncoded FLOPs
  instead of ~d x. Gradients, optimizer updates and loss trajectories
  match the replicated path to float32 tolerance
  (tests/test_dedup.py); only the wall-clock differs.
* **Compressed combine** (``make_train_step(compress=...)``): the
  bandwidth-bound regime, where shipping full-precision g_j costs a
  d-fold comms tax exactly where dedup already closed the FLOP tax.
  Each machine's (or, on the dedup path, each unique block's) gradient
  is quantized by a ``core.compress`` codec (int8 / signSGD sign) with
  per-worker error feedback, and the decode-weighted combine runs
  directly on the quantized payload through the fused
  ``quantized_combine`` kernel -- dequantize, w-weight and reduce in
  one pass, never materialising float32 per-machine gradients. The
  step's state grows a residual pytree next to ``opt_state`` (the
  telescoping error-feedback memory, checkpointed with it); at codec
  'none' the path pins to the float32 step at the per-machine-grads
  tolerance of tests/test_dist.py, and under int8/sign/sign_packed to
  the quantization bound (tests/test_compress.py).
* **Streaming combine**
  (``make_manual_collective_train_step(streaming_chunk=...)``): the
  memory-bound regime. The combine ``sum_j w_j g_j`` is linear in the
  per-machine gradients, so it never needs them all live at once --
  the same identity Charles et al. use to analyse the decoded
  gradient lets the reduction stream machine-by-machine. A
  ``lax.scan`` walks the machine axis in chunks (one chunk per worker
  shard per step, so data parallelism is preserved), computes that
  chunk's gradients, runs the per-chunk coded (or quantized/packed)
  allreduce, and folds the result into a single float32 accumulator
  pytree: peak live-gradient memory drops from the materialising
  path's m-rows-at-once to O(chunk). The scan reassociates the sum,
  so this path pins to the materialising manual step at float32
  tolerance (tests/test_streaming.py), and
  ``benchmarks/train_step.py`` records both paths' compiled peak
  bytes to show the drop is real.

``coded_allreduce`` / ``make_manual_collective_train_step`` keep the
combine as an explicit shard_map psum for runs that want manual
control over the reduction instead of the GSPMD-inserted one;
``quantized_coded_allreduce`` is the same collective carrying the
quantized payload (each shard dequant-combines its local machines,
then one float32 psum of the partial combines), and
``packed_sign_coded_allreduce`` the variant whose wire payload is the
``sign_packed`` codec's 1-bit planes. All three share one shard_map
skeleton (``_coded_psum_allreduce``).

Host side, ``CodingRuntime`` bridges ``repro.core``'s oracle into the
training loop: it instantiates the assignment (expander / FRC /
uncoded), pulls one alive mask per step from its ``MaskSource``, and
emits per-step w* through the shared
``core.step_weights`` pipeline (decode dispatch + alpha-bar debias via
the batched engine), memoising repeated masks -- stagnant stragglers
(the paper's cluster observation, the Markov model here) make the
decode cache hit almost every step. ``weights_lookahead`` pre-samples
a horizon of masks and decodes the novel ones in one
``decode_batch`` call, for pipelined loops that refuse even the
per-step cache-lookup latency.

Observed-mask execution model (elastic fault tolerance)
-------------------------------------------------------

Where the masks come from is a ``core.step_weights.MaskSource``:
*sampled* (the default -- a synthetic ``core.stragglers`` process,
bit-identical to the pre-abstraction inline RNG), *observed* (the
driver pushes masks the ``dist.failures.HeartbeatMonitor`` derived
from per-machine completion timestamps -- a miss means that machine
shipped no gradient this round, so the decode routes around it
exactly as it would a sampled straggler), or *replayed* (a recorded
(T, m) stream, for deterministic re-execution of failure traces).
Everything downstream of ``step_weights()`` is source-agnostic.

Observed masks add one genuinely new transition: permanent death.
When the monitor declares a machine dead, ``elastic_reassign``
re-draws the code over the m-1 survivors -- seed derived by the pure
``elastic_seed(seed, generation)``, replication degraded
deterministically to the largest feasible degree
(``elastic_coding``) -- and the driver rebuilds its per-generation
machinery (batcher, block shardings via the divisibility fallback,
jitted step) around the live {params, opt_state}. Because both the
re-assignment and a from-scratch launch on the survivors derive the
same coding from (seed, generation) and data batches are a pure
function of the step index, the elastic continuation is bit-identical
to a fresh run started from the same state (tests/test_elastic.py).
Lookahead prefetching only applies to sampled streams; observed masks
decode per step, since the future cannot be pre-observed.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from jax.sharding import PartitionSpec as P

from repro import spans
from repro.configs.base import CodingConfig, ModelConfig
import repro.core.compress as compress_mod
import repro.core.step_weights as sw
from repro.core.adaptive import (DecodingPolicy, OnlineStragglerEstimator,
                                 PolicyDecision, make_policy)
from repro.core.assignment import (Assignment, bibd_assignment,
                                   cyclic_mds_assignment,
                                   expander_assignment, frc_assignment,
                                   random_matching_assignment,
                                   uncoded_assignment)
from repro.kernels.coded_combine import ops as cc_ops
from repro.models import model as M
from repro.optim import optimizers as opt_mod

from .sharding import data_axes


# ---------------------------------------------------------------------------
# Coded loss and train/prefill/serve steps
# ---------------------------------------------------------------------------


def coded_loss_fn(params, coded_batch: Dict[str, jnp.ndarray],
                  w: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    """Per-block weighted coded loss; grad == sum_j w_j g_j (Eq. 1).

    coded_batch leaves are (m, load, bs, ...) with a ``block_weight``
    (m, load) mask (0 on padding slots of irregular assignments); w is
    the (m,) decoding weights. The machine/load/batch axes flatten into
    one forward pass, so the machine axis shards over the data axes of
    the mesh without any per-machine python loop.
    """
    bw = coded_batch["block_weight"]                      # (m, load)
    m, load = bw.shape
    flat = {k: v.reshape((-1,) + v.shape[3:])
            for k, v in coded_batch.items() if k != "block_weight"}
    per_seq = M.train_loss(params, flat, cfg, per_example=True)
    per_block = per_seq.reshape(m, load, -1).sum(axis=2)  # (m, load)
    norm = coded_batch["labels"].size
    return (w[:, None] * bw * per_block).sum() / norm


def coded_loss_fn_dedup(params, block_batch: Dict[str, jnp.ndarray],
                        v: jnp.ndarray, cfg: ModelConfig,
                        norm_scale: float = 1.0) -> jnp.ndarray:
    """Per-unique-block weighted coded loss; grad == sum_j w_j g_j.

    block_batch leaves are (n, block_rows, ...) unique blocks
    (``CodedBatcher.unique_blocks``); v is the (n,) per-block weights
    ``A @ w`` (``core.step_weights.block_weights``). Since the
    replicated combine is ``sum_j w_j sum_l bw_jl L_jl = sum_i v_i
    L_i``, this computes the identical loss/gradient from one forward
    pass per block -- ~1x the uncoded FLOPs instead of ~d x.

    ``norm_scale`` reproduces the replicated path's normalisation: the
    replicated batch counts m*load block slots of labels (padding
    included), the dedup batch counts n, so passing
    ``dedup_norm_scale(assignment) = m*load/n`` makes losses (not just
    gradients-up-to-scale) match ``coded_loss_fn`` exactly.
    """
    labels = block_batch["labels"]
    n = labels.shape[0]
    flat = {k: x.reshape((-1,) + x.shape[2:])
            for k, x in block_batch.items()}
    per_seq = M.train_loss(params, flat, cfg, per_example=True)
    per_block = per_seq.reshape(n, -1).sum(axis=1)   # (n,)
    norm = labels.size * norm_scale
    return (v * per_block).sum() / norm


def dedup_norm_scale(assignment: Assignment) -> float:
    """m*load/n: the factor that aligns the dedup loss normalisation
    with the replicated batch's (padded) label count."""
    return assignment.m * assignment.load / assignment.n


def compress_combine_tree(grads, residual, w, codec, *,
                          error_feedback: bool = True):
    """Quantize per-row gradients and run the fused combine per leaf.

    ``grads`` leaves carry a leading row axis (m machines or n unique
    blocks); ``residual`` is the matching error-feedback pytree
    (``core.compress.init_state``); ``w`` the (rows,) decode weights
    (machine w or block v = A @ w). Per leaf: compress ``g + e``
    row-wise, combine the quantized payload through
    ``quantized_combine`` -- or ``packed_sign_combine`` for a packed
    codec, which unpacks the 1-bit planes inside the kernel -- (the
    float32 per-row gradients are never materialised past this
    point), and keep ``e' = (g + e) - dequant``. Returns (combined
    float32 tree, new residual tree).
    """
    g_leaves, treedef = jax.tree.flatten(grads)
    r_leaves = treedef.flatten_up_to(residual)
    outs, new_rs = [], []
    for g, r in zip(g_leaves, r_leaves):
        rows = g.shape[0]
        flat = g.reshape(rows, -1).astype(jnp.float32)
        d = flat.shape[1]
        pre = flat + r.reshape(rows, -1) if error_feedback else flat
        q, s = codec.compress(pre)
        if codec.packed:
            outs.append(cc_ops.packed_sign_combine(q, s, w, d)
                        .reshape(g.shape[1:]))
        else:
            outs.append(cc_ops.quantized_combine(q, s, w)
                        .reshape(g.shape[1:]))
        new_rs.append(
            (pre - codec.decompress(q, s, d=d)).reshape(g.shape)
            if error_feedback else r)
    return (jax.tree.unflatten(treedef, outs),
            jax.tree.unflatten(treedef, new_rs))


def _per_machine_values_and_grads(params, batch, cfg, norm=None):
    """vmapped per-machine (loss_j, g_j) over the replicated (m, load,
    ...) batch -- the materialised form both the manual collective and
    the compressed replicated path reduce. ``norm`` overrides the loss
    normaliser (the streaming path passes the *full* batch's label
    count while feeding machine chunks)."""
    bw = batch["block_weight"]
    load = bw.shape[1]
    if norm is None:
        norm = batch["labels"].size

    @jax.named_scope("coded.loss")
    def machine_loss(p, mb, bw_j):
        flat = {k: x.reshape((-1,) + x.shape[2:])
                for k, x in mb.items()}
        per_seq = M.train_loss(p, flat, cfg, per_example=True)
        per_block = per_seq.reshape(load, -1).sum(axis=1)
        return (bw_j * per_block).sum() / norm

    data = {k: v for k, v in batch.items() if k != "block_weight"}
    return jax.vmap(
        lambda mb, bw_j: jax.value_and_grad(machine_loss)(
            params, mb, bw_j))(data, bw)


def _finish(optimizer: opt_mod.Optimizer, params, opt_state, grads, w, *,
            dedup: bool = False, aw=None, **metrics):
    """The tail every train step shares: the optimizer update, then the
    gradient's norm and the debias divisor ``alpha_bar`` (mean(v) on
    the dedup path, (colsum(A)/n) . w via ``aw`` on the replicated one,
    none without it) beside the step's other on-device ``metrics``."""
    with jax.named_scope("coded.optimizer"):
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = opt_mod.apply_updates(params, updates)
    with jax.named_scope("coded.metrics"):
        metrics["grad_norm"] = opt_mod.global_norm(grads)
        if dedup:
            metrics["alpha_bar"] = w.mean()
        elif aw is not None:
            metrics["alpha_bar"] = jnp.dot(aw, w)
    return params, opt_state, metrics


def make_train_step(cfg: ModelConfig, optimizer: opt_mod.Optimizer,
                    n_microbatches: int = 1, *, dedup: bool = False,
                    norm_scale: float = 1.0, alpha_weights=None,
                    compress=None, error_feedback: bool = True):
    """(params, opt_state, coded_batch, w) -> (params, opt_state,
    metrics).

    ``n_microbatches`` > 1 accumulates gradients over equal splits of
    the per-block batch axis under ``lax.scan`` (constant HLO size,
    rematerialised activations): the mean of per-microbatch losses /
    gradients equals the single-shot step because the coded loss is a
    normalised sum over sequences. Accumulation is deliberately
    float32 -- exact for the float32 param configs shipped here, and
    the standard higher-precision accumulator if params ever go bf16
    (where the single-shot step would differ by the grads' bf16
    rounding, not by this sum).

    ``dedup=True`` builds the deduplicated-block step instead: the
    batch is ``CodedBatcher.unique_blocks`` output and ``w`` is the
    per-block ``v = A @ w`` (pass ``norm_scale=dedup_norm_scale(A)``
    to keep loss values aligned with the replicated path).

    Metrics stay on device so pipelined loops never block on them:
    ``alpha_bar`` (the debias divisor the driver used to fetch as a
    host-side ``A @ w`` every step) is folded into the metrics dict --
    ``mean(v)`` directly on the dedup path, ``(colsum(A)/n) . w`` via
    ``alpha_weights`` on the replicated one (omitted if None).

    ``compress`` (a ``core.compress`` codec name or Codec) switches to
    the compressed-combine execution model: the step's signature grows
    the error-feedback state, ``(params, opt_state, comp_state, batch,
    w) -> (params, opt_state, comp_state, metrics)``. Per-row (machine
    or unique-block) gradients are materialised by a vmapped backward
    pass, quantized with error feedback, and reduced through the fused
    ``quantized_combine`` kernel; metrics gain ``comm_bytes`` (the
    payload the combine consumed this step, a trace-time constant).
    Incompatible with ``n_microbatches > 1`` (the residual update is
    defined per full-batch compression round).
    """
    nm = int(n_microbatches)
    if nm < 1:
        raise ValueError("n_microbatches must be >= 1")
    aw = (None if alpha_weights is None
          else jnp.asarray(alpha_weights, jnp.float32))

    if compress is not None:
        if nm != 1:
            raise ValueError("compress does not compose with "
                             "n_microbatches > 1")
        codec = compress_mod.get_codec(compress)

        def compressed_step(params, opt_state, comp_state, batch, w):
            if dedup:
                labels = batch["labels"]
                norm = labels.size * norm_scale

                @jax.named_scope("coded.loss")
                def block_loss(p, blk):
                    per_seq = M.train_loss(p, blk, cfg,
                                           per_example=True)
                    return per_seq.sum() / norm

                losses, grads = jax.vmap(
                    lambda blk: jax.value_and_grad(block_loss)(
                        params, blk))(batch)
            else:
                losses, grads = _per_machine_values_and_grads(
                    params, batch, cfg)
            loss = (w * losses).sum()
            with jax.named_scope("coded.combine"):
                combined, new_resid = compress_combine_tree(
                    grads, comp_state["residual"], w, codec,
                    error_feedback=error_feedback)
            rows = w.shape[0]
            comm = compress_mod.comm_bytes_per_step(
                codec, int(rows), params)
            params, opt_state, metrics = _finish(
                optimizer, params, opt_state, combined, w, dedup=dedup,
                aw=aw, loss=loss, comm_bytes=jnp.asarray(comm, jnp.float32))
            return params, opt_state, {"residual": new_resid}, metrics

        return compressed_step

    @jax.named_scope("coded.loss")
    def loss_fn(p, b, wv):
        if dedup:
            return coded_loss_fn_dedup(p, b, wv, cfg,
                                       norm_scale=norm_scale)
        return coded_loss_fn(p, b, wv, cfg)

    def step(params, opt_state, batch, w):
        if nm == 1:
            loss, grads = jax.value_and_grad(loss_fn)(params, batch, w)
        else:
            # microbatch split along the per-block batch axis:
            # replicated leaves are (m, load, bs, ...), dedup (n, bs, ...)
            bax = 1 if dedup else 2
            bw = None if dedup else batch["block_weight"]

            def to_micro(leaf):
                bs_ = leaf.shape[bax]
                if bs_ % nm:
                    raise ValueError(
                        f"block batch {bs_} not divisible by "
                        f"{nm} microbatches")
                x = leaf.reshape(leaf.shape[:bax] + (nm, bs_ // nm)
                                 + leaf.shape[bax + 1:])
                return jnp.moveaxis(x, bax, 0)

            micro = {k: to_micro(v) for k, v in batch.items()
                     if k != "block_weight"}

            def body(carry, mb):
                g_acc, l_acc = carry
                if bw is not None:
                    mb = dict(mb)
                    mb["block_weight"] = bw
                l, g = jax.value_and_grad(loss_fn)(params, mb, w)
                return (jax.tree.map(jnp.add, g_acc, g), l_acc + l), None

            zeros = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            (gsum, lsum), _ = jax.lax.scan(
                body, (zeros, jnp.zeros((), jnp.float32)), micro)
            grads = jax.tree.map(lambda g: g / nm, gsum)
            loss = lsum / nm
        return _finish(optimizer, params, opt_state, grads, w, dedup=dedup,
                       aw=aw, loss=loss)

    return step


def make_prefill_step(cfg: ModelConfig):
    """(params, batch) -> last-position logits (B, V_pad)."""
    def step(params, batch):
        return M.prefill(params, batch["tokens"], cfg,
                         prefix=batch.get("prefix"),
                         src=batch.get("src"))
    return step


def make_serve_step(cfg: ModelConfig, window: Optional[int] = None):
    """(params, token, cache) -> (logits, new_cache)."""
    def step(params, token, cache):
        return M.decode_step(params, token, cache, cfg, window=window)
    return step


def _coded_psum_allreduce(mesh, local_combine_fn, trees, w: jnp.ndarray):
    """The one shard_map skeleton the coded-allreduce family shares.

    Every payload tree in ``trees`` (and ``w``) carries a leading
    (global) machine axis sharded over the (pod, data) worker axes;
    ``local_combine_fn(*local_trees, w_local)`` reduces each shard's
    local machines to one partial combine, and a psum over the worker
    axes produces the replicated global result. The variants differ
    only in what crosses the machine axis (float32 gradients, int8
    payloads, packed sign bit-planes) and which fused kernel reduces
    it locally.
    """
    axes = data_axes(mesh)
    lead = axes if len(axes) > 1 else axes[0]
    in_specs = tuple(jax.tree.map(lambda _: P(lead), t) for t in trees)

    def body(*args):
        *local, w_local = args
        out = local_combine_fn(*local, w_local)
        return jax.tree.map(lambda x: jax.lax.psum(x, axes), out)

    return jax.shard_map(body, mesh=mesh, in_specs=(*in_specs, P(lead)),
                         out_specs=jax.tree.map(lambda _: P(), trees[0]))(
        *trees, w)


def coded_allreduce(grads, w: jnp.ndarray, mesh):
    """The paper combine as an explicit shard_map collective.

    ``grads`` leaves carry a leading (global) machine axis of size m
    sharded over the (pod, data) axes; ``w`` is the (m,) decoding
    weights sharded the same way. Each shard w-weights and sums its
    local machines through the ``coded_combine`` kernel, then a psum
    over the worker axes produces the replicated global
    ``sum_j w_j g_j``.
    """
    return _coded_psum_allreduce(mesh, cc_ops.coded_combine_tree,
                                 (grads,), w)


def quantized_coded_allreduce(q_tree, scale_tree, w: jnp.ndarray, mesh):
    """``coded_allreduce`` carrying the quantized payload.

    ``q_tree`` leaves are (m, ...) codec payloads (int8 for int8/sign,
    float32 for 'none') with matching (m,) per-machine scales in
    ``scale_tree``, both sharded over the worker axes like the float32
    gradients would be -- so the bytes crossing the machine axis are
    the codec's wire format, not float32. Each shard runs the fused
    ``quantized_combine`` over its local machines and a single float32
    psum of the partial combines produces the replicated global
    ``sum_j w_j * scale_j * q_j``.
    """
    return _coded_psum_allreduce(mesh, cc_ops.quantized_combine_tree,
                                 (q_tree, scale_tree), w)


def packed_sign_coded_allreduce(q_tree, scale_tree, w: jnp.ndarray,
                                mesh, shapes):
    """``coded_allreduce`` carrying the 1-bit packed sign payload.

    ``q_tree`` leaves are (m, ceil(size/8)) uint8 bit-planes (the
    ``sign_packed`` codec's wire format -- 1/32 of the float32 bytes
    crossing the machine axis); ``shapes`` is the matching pytree of
    combined-output shapes, which the packed payload cannot carry
    itself. Each shard runs the fused ``packed_sign_combine`` (unpack,
    +-1, weight, reduce in one pass) over its local machines, then the
    shared float32 psum.
    """
    def local_combine(qt, st, w_local):
        return cc_ops.packed_sign_combine_tree(qt, st, w_local, shapes)

    return _coded_psum_allreduce(mesh, local_combine,
                                 (q_tree, scale_tree), w)


def alpha_bar_weights(assignment: Assignment) -> np.ndarray:
    """(m,) vector a with a . w == mean(A @ w): the on-device form of
    the alpha-bar debias divisor (colsum(A)/n), so train steps can
    report it in metrics instead of the driver syncing ``A @ w`` to
    the host every step."""
    return (assignment.A.sum(axis=0) / assignment.n).astype(np.float32)


def _quantize_rows(grads, residual, codec, error_feedback: bool):
    """Row-wise quantize of g (+ residual) per leaf, flat payloads.

    Returns (q_tree, scale_tree, new_residual_tree, shapes_tree):
    payload leaves stay flat (rows, D) -- or (rows, ceil(D/8)) for a
    packed codec -- and ``shapes_tree`` carries each leaf's
    combined-output shape (the original shape minus the row axis) for
    the post-combine reshape the flat payload can't express itself.
    """
    g_leaves, treedef = jax.tree.flatten(grads)
    r_leaves = treedef.flatten_up_to(residual)
    q_l, s_l, r_l, shp_l = [], [], [], []
    for g, r in zip(g_leaves, r_leaves):
        rows = g.shape[0]
        flat = g.reshape(rows, -1).astype(jnp.float32)
        pre = flat + r.reshape(rows, -1) if error_feedback else flat
        q, s = codec.compress(pre)
        q_l.append(q)
        s_l.append(s)
        r_l.append(
            (pre - codec.decompress(q, s, d=flat.shape[1]))
            .reshape(g.shape) if error_feedback else r)
        shp_l.append(tuple(g.shape[1:]))
    unflatten = treedef.unflatten
    return (unflatten(q_l), unflatten(s_l), unflatten(r_l),
            unflatten(shp_l))


def _compressed_allreduce(q_tree, scale_tree, w, codec, shapes, mesh):
    """Codec-dispatching wire collective over flat row payloads."""
    if codec.packed:
        return packed_sign_coded_allreduce(q_tree, scale_tree, w, mesh,
                                           shapes)
    out = quantized_coded_allreduce(q_tree, scale_tree, w, mesh)
    treedef = jax.tree.structure(out)
    return treedef.unflatten(
        [x.reshape(s) for x, s in zip(jax.tree.leaves(out),
                                      treedef.flatten_up_to(shapes))])


def _n_worker_shards(mesh) -> int:
    m = mesh.shape["data"]
    if "pod" in mesh.axis_names:
        m *= mesh.shape["pod"]
    return m


def _to_stream_chunks(leaf, n_shards: int, chunk: int):
    """(m, ...) -> (T, n_shards * chunk, ...) machine regrouping.

    The machine axis is block-sharded over the worker shards (shard s
    owns machines [s*m/W, (s+1)*m/W)), so a scan over contiguous
    machine chunks would serialise the shards. This regrouping makes
    scan step t carry ``chunk`` consecutive machines *from every
    shard* -- full data parallelism per step, O(chunk) live gradients
    per device -- and the slice's leading axis stays block-contiguous
    per shard, so the per-chunk allreduce's P(lead) specs still hold.
    """
    m = leaf.shape[0]
    per = m // n_shards
    x = leaf.reshape((n_shards, per // chunk, chunk) + leaf.shape[1:])
    x = jnp.moveaxis(x, 1, 0)
    return x.reshape((per // chunk, n_shards * chunk) + leaf.shape[1:])


def _from_stream_chunks(leaf, n_shards: int, chunk: int):
    """(T, n_shards * chunk, ...) -> (m, ...): exact inverse of
    ``_to_stream_chunks`` (the residual pytree's way home)."""
    t = leaf.shape[0]
    x = leaf.reshape((t, n_shards, chunk) + leaf.shape[2:])
    x = jnp.moveaxis(x, 0, 1)
    return x.reshape((n_shards * t * chunk,) + leaf.shape[2:])


def make_manual_collective_train_step(cfg: ModelConfig,
                                      optimizer: opt_mod.Optimizer,
                                      mesh, alpha_weights=None,
                                      compress=None,
                                      error_feedback: bool = True,
                                      streaming_chunk: Optional[int]
                                      = None):
    """Replicated-path train step whose combine is the explicit
    ``coded_allreduce`` shard_map psum instead of the GSPMD-inserted
    one (the ROADMAP manual-vs-gspmd comparison).

    Unlike ``make_train_step`` -- where autodiff of the w-weighted
    loss fuses the per-machine gradients into one backward pass -- the
    manual route must materialise what the collective reduces: per-
    machine gradients g_j via a vmapped value_and_grad over the
    machine axis (same backward FLOPs, m x the gradient memory), then
    ``sum_j w_j g_j`` as coded_combine + psum over the worker axes.
    That makes it the fidelity-first option (the reduction is
    inspectable and the per-machine g_j exist as tensors, as on a real
    cluster), not the fast one; ``benchmarks/train_step.py`` carries a
    ``collective: manual`` row tracking exactly what that costs.

    ``compress`` routes the combine through
    ``quantized_coded_allreduce`` (or, for the packed 1-bit codec,
    ``packed_sign_coded_allreduce``) instead: the per-machine
    gradients are quantized (with error feedback) *before* the
    collective, so what crosses the worker axes is the codec's wire
    payload. As in ``make_train_step``, the compressed step's
    signature carries the residual state as a third positional
    argument.

    ``streaming_chunk`` bounds how many of the m per-machine gradients
    are ever live at once: a ``lax.scan`` walks the machine axis in
    groups of ``chunk`` machines per worker shard (``_to_stream_chunks``
    regroups the block-sharded machine axis so every scan step keeps
    all shards busy), runs the per-chunk collective, and accumulates
    into one float32 pytree -- the combine is linear in the g_j, so
    streaming only reassociates the sum (pinned to the materialising
    step at float32 tolerance in tests/test_streaming.py). Composes
    with ``compress``: quantization, error feedback and the wire
    collective all happen per chunk, and the residual chunks are
    scanned out and restored to machine order. Requires m divisible by
    (worker shards) * chunk.
    """
    aw = (None if alpha_weights is None
          else jnp.asarray(alpha_weights, jnp.float32))
    codec = (None if compress is None
             else compress_mod.get_codec(compress))
    if streaming_chunk is not None and int(streaming_chunk) < 1:
        raise ValueError("streaming_chunk must be >= 1")

    def finish(params, opt_state, loss, grads, w, **extra):
        return _finish(optimizer, params, opt_state, grads, w, aw=aw,
                       loss=loss, **extra)

    if streaming_chunk is not None:
        chunk = int(streaming_chunk)
        n_shards = _n_worker_shards(mesh)

        def _check_divisible(m):
            if m % (n_shards * chunk):
                raise ValueError(
                    f"streaming needs m divisible by worker shards x "
                    f"chunk = {n_shards} x {chunk}, got m={m}")

        def _scan_combine(params, batch, w, residual):
            """Shared streaming core: scan machine chunks, accumulate
            the (possibly quantized) combine and the w-weighted loss;
            returns (grads, loss, new_residual-or-None)."""
            m = w.shape[0]
            _check_divisible(m)
            norm = batch["labels"].size
            b_xs = {k: _to_stream_chunks(v, n_shards, chunk)
                    for k, v in batch.items()}
            w_xs = _to_stream_chunks(w, n_shards, chunk)
            xs = (b_xs, w_xs)
            if residual is not None:
                xs += (jax.tree.map(
                    lambda r: _to_stream_chunks(r, n_shards, chunk),
                    residual),)

            def body(carry, xs_t):
                g_acc, l_acc = carry
                cb, w_c = xs_t[0], xs_t[1]
                losses, grads = _per_machine_values_and_grads(
                    params, cb, cfg, norm=norm)
                with jax.named_scope("coded.combine"):
                    if codec is None:
                        contrib = coded_allreduce(grads, w_c, mesh)
                        new_r = None
                    else:
                        q_t, s_t, new_r, shapes = _quantize_rows(
                            grads, xs_t[2], codec, error_feedback)
                        contrib = _compressed_allreduce(
                            q_t, s_t, w_c, codec, shapes, mesh)
                    g_acc = jax.tree.map(jnp.add, g_acc, contrib)
                l_acc = l_acc + (w_c * losses).sum()
                return (g_acc, l_acc), new_r

            zeros = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            (grads, loss), r_ys = jax.lax.scan(
                body, (zeros, jnp.zeros((), jnp.float32)), xs)
            if residual is not None:
                r_ys = jax.tree.map(
                    lambda r: _from_stream_chunks(r, n_shards, chunk),
                    r_ys)
            return grads, loss, r_ys

        if codec is not None:
            def streaming_compressed_step(params, opt_state, comp_state,
                                          batch, w):
                grads, loss, new_resid = _scan_combine(
                    params, batch, w, comp_state["residual"])
                comm = compress_mod.comm_bytes_per_step(
                    codec, int(w.shape[0]), params)
                params, opt_state, metrics = finish(
                    params, opt_state, loss, grads, w,
                    comm_bytes=jnp.asarray(comm, jnp.float32))
                return params, opt_state, {"residual": new_resid}, \
                    metrics

            return streaming_compressed_step

        def streaming_step(params, opt_state, batch, w):
            grads, loss, _ = _scan_combine(params, batch, w, None)
            return finish(params, opt_state, loss, grads, w)

        return streaming_step

    if codec is not None:
        def compressed_step(params, opt_state, comp_state, batch, w):
            losses, grads = _per_machine_values_and_grads(
                params, batch, cfg)
            loss = (w * losses).sum()
            with jax.named_scope("coded.combine"):
                q_tree, s_tree, new_resid, shapes = _quantize_rows(
                    grads, comp_state["residual"], codec, error_feedback)
                combined = _compressed_allreduce(q_tree, s_tree, w, codec,
                                                 shapes, mesh)
            comm = compress_mod.comm_bytes_per_step(
                codec, int(w.shape[0]), params)
            params, opt_state, metrics = finish(
                params, opt_state, loss, combined, w,
                comm_bytes=jnp.asarray(comm, jnp.float32))
            return params, opt_state, {"residual": new_resid}, metrics

        return compressed_step

    def step(params, opt_state, batch, w):
        losses, grads = _per_machine_values_and_grads(params, batch,
                                                      cfg)
        with jax.named_scope("coded.combine"):
            grads = coded_allreduce(grads, w, mesh)   # (m, ...) -> combine
        loss = (w * losses).sum()
        return finish(params, opt_state, loss, grads, w)

    return step


# ---------------------------------------------------------------------------
# Host-side coding runtime
# ---------------------------------------------------------------------------


def make_assignment(coding: CodingConfig, m: int) -> Assignment:
    """Instantiate the block assignment for m coded workers."""
    if coding.scheme == "expander":
        return expander_assignment(m, coding.replication,
                                   vertex_transitive=True,
                                   seed=coding.seed)
    if coding.scheme == "frc":
        return frc_assignment(m, coding.replication)
    if coding.scheme == "uncoded":
        return uncoded_assignment(m)
    if coding.scheme == "cyclic_mds":
        return cyclic_mds_assignment(m, coding.replication)
    if coding.scheme == "bibd":
        # Solve for the design whose *machine* count is m. With
        # replication r = coding.replication: the affine plane of
        # order q = r - 1 has q^2 + q = (r-1)r machines, else a
        # symmetric design puts one machine per point (v = m, k = r).
        r = coding.replication
        if m == (r - 1) * r:
            return bibd_assignment((r - 1) ** 2, r - 1, design="affine")
        return bibd_assignment(m, r, design="symmetric")
    if coding.scheme == "random_regular":
        return random_matching_assignment(m, coding.replication,
                                          seed=coding.seed)
    raise ValueError(f"unknown scheme {coding.scheme!r} "
                     "(expander | frc | uncoded | cyclic_mds | bibd "
                     "| random_regular)")


def elastic_seed(seed: int, generation: int) -> int:
    """The seed for elastic generation g of a run seeded ``seed``.

    A pure function of (seed, generation) -- both the elastic
    re-assignment in a running driver AND a fresh driver launched on
    the survivors must derive the same seed, or the differential pin
    (elastic trajectory == fresh-run trajectory) could not hold."""
    if generation < 0:
        raise ValueError("generation must be >= 0")
    return seed + 1_000_003 * generation


def elastic_coding(coding: CodingConfig, m_new: int,
                   generation: int) -> CodingConfig:
    """The CodingConfig for generation ``generation`` over ``m_new``
    survivors.

    Scheme divisibility can break when machines die (expander needs
    d | 2m', FRC d | m'), so the replication degree degrades to the
    largest feasible d' <= d -- gracefully, the way the sharding
    rules' divisibility fallback degrades specs -- rather than
    refusing to continue. Deterministic, so the fresh-run side of the
    differential pin reconstructs the identical assignment."""
    if m_new < 1:
        raise ValueError("need at least one survivor")
    seed = elastic_seed(coding.seed, generation)
    if m_new == 1 or (coding.scheme == "expander" and m_new == 2):
        # A single survivor cannot carry a replicated code, and the
        # smallest d-regular graph scheme is the 3-edge cycle (two
        # vertices collapse to a double edge).
        return dataclasses.replace(coding, scheme="uncoded",
                                   replication=1, seed=seed)
    d = min(coding.replication, m_new)
    if coding.scheme == "expander":
        # d = 2 (the cycle) always divides 2m', so the loop bottoms
        # out at a valid graph scheme for m' >= 3.
        while d > 2 and (2 * m_new) % d:
            d -= 1
        d = max(d, 2)
    elif coding.scheme == "frc":
        while d > 1 and m_new % d:
            d -= 1
    return dataclasses.replace(coding, replication=d, seed=seed)


def elastic_reassign(runtime: "CodingRuntime", dead, *,
                     generation: int,
                     mask_source: "Optional[sw.MaskSource]" = None
                     ) -> "CodingRuntime":
    """Re-draw the code over the survivors after permanent deaths.

    ``dead`` is the dead logical machine ids *of the current runtime*
    (the driver's SurvivorMap translates original ids). Returns a
    fresh ``CodingRuntime`` over m' = m - len(dead) machines with the
    generation-derived seed: new expander assignment, new debias
    scale, empty decode cache. Training resumes from the live
    {params, opt_state} -- the block shards remap through the existing
    ``dist/sharding.block_shardings`` divisibility-fallback rules when
    the driver rebuilds its jitted step -- and the post-death
    trajectory is bit-identical to a fresh run launched on the
    survivors from the same restored state (tests/test_elastic.py).
    """
    dead = np.atleast_1d(np.asarray(dead, dtype=np.int64))
    if np.unique(dead).size != dead.size:
        raise ValueError("duplicate dead machine ids")
    if dead.size and (dead.min() < 0 or dead.max() >= runtime.m):
        raise ValueError(f"dead ids {dead.tolist()} out of range for "
                         f"m={runtime.m}")
    m_new = runtime.m - int(dead.size)
    coding = elastic_coding(runtime.coding, m_new, generation)
    return CodingRuntime(coding, m_new, debias=runtime.debias,
                         debias_trials=runtime.debias_trials,
                         cache_size=runtime.cache_size,
                         mask_source=mask_source,
                         adaptive=runtime.adaptive)


@dataclasses.dataclass
class CodingRuntime:
    """Host bridge: assignment + straggler process + per-step weights.

    One instance per run. ``step_weights()`` samples this round's alive
    mask from the configured ``core.stragglers`` model and returns the
    debiased decoding weights w (w_j = 0 on stragglers) for the train
    step, memoised by mask: under stagnant straggler processes
    (markov / adversarial) the same mask repeats for many consecutive
    rounds and decoding drops out of the step latency entirely.

    The alpha-bar debias scale is estimated once at construction --
    optimal decoding shrinks alpha below 1 on average, and the scale
    makes the expected update unbiased without per-step work. For the
    stochastic models it is one ``batched_alpha`` decode of a Bernoulli
    mask batch (``core.step_weights.debias_scale_mc``); the adversarial
    model replays a single fixed mask, so its exact scale comes from
    that mask's own alpha. Fixed decoding is already unbiased by
    construction, so the scale stays 1 there.
    """

    coding: CodingConfig
    m: int
    debias: bool = True
    debias_trials: int = 256
    cache_size: int = 4096
    mask_source: Optional[sw.MaskSource] = None
    # Per-step decoding policy (core.adaptive): None keeps the
    # pre-adaptive fixed-ahead-of-time behaviour bit-identically; a
    # policy spec ("adaptive" | "always_optimal" | "always_fixed" | a
    # DecodingPolicy) makes every round decide its decoder from the
    # online straggler estimate before the round's mask is observed.
    adaptive: Optional[object] = None

    def __post_init__(self):
        self.assignment = make_assignment(self.coding, self.m)
        self.model = sw.make_straggler_model(
            self.assignment, self.coding.straggler_model,
            self.coding.straggler_p)
        self.rng = np.random.default_rng(self.coding.seed)
        if self.mask_source is None:
            # Default: the synthetic simulation path, wrapping this
            # runtime's own (model, rng) pair so the RNG stream is
            # bit-identical to the pre-abstraction code.
            self.mask_source = sw.SampledMaskSource(self.model,
                                                   self.rng, self.m)
        elif self.mask_source.m != self.m:
            raise ValueError(
                f"mask source is over m={self.mask_source.m} machines, "
                f"runtime has m={self.m}")
        self.policy: Optional[DecodingPolicy] = None
        self.estimator: Optional[OnlineStragglerEstimator] = None
        self.last_decision: Optional[PolicyDecision] = None
        self.decision_counts: Dict[str, int] = {}
        if self.adaptive is not None:
            self.policy = make_policy(self.adaptive,
                                      p=self.coding.straggler_p)
            # The configured p seeds the estimator's prior; the
            # observed stream takes over within a few rounds.
            self.estimator = OnlineStragglerEstimator(
                self.m, prior_p=min(max(self.coding.straggler_p, 0.0),
                                    0.99))
        self.scale = 1.0
        # An adaptive runtime may decode optimally on any step
        # whatever the configured default, so it needs the optimal-
        # decode debias scale too; the scale applies only to optimal
        # decodes (Section VIII fixed weights are unbiased by
        # construction), and its value is a pure function of
        # (assignment, p, seed) -- identical to the non-adaptive
        # runtime's, which keeps always_optimal bit-identical.
        if self.debias and (self.coding.decoding == "optimal"
                            or self.policy is not None):
            if self.coding.straggler_model == "adversarial":
                # The attack mask is deterministic: the exact debias
                # factor is sqrt(n)/|alpha| of that one decode.
                _, alpha = sw.step_weights(
                    self.assignment, self.model.sample(self.rng),
                    method="optimal")
                self.scale = float(
                    np.sqrt(alpha.size) /
                    max(np.linalg.norm(alpha), 1e-30))
            else:
                # Offset the seed: bernoulli_uniforms(seed) replays the
                # exact uniform stream the training masks consume, so
                # the same seed would fit the scale in-sample on the
                # run's own first `debias_trials` masks.
                self.scale = sw.debias_scale_mc(
                    self.assignment, p=self.coding.straggler_p,
                    trials=self.debias_trials,
                    seed=self.coding.seed + 0x5EED)
        self._cache: Dict[bytes, np.ndarray] = {}
        self.decode_calls = 0
        self.steps_sampled = 0

    def skip(self, rounds: int) -> None:
        """Fast-forward the mask stream by ``rounds`` rounds without
        decoding -- the checkpoint-resume path: a restored run calls
        ``skip(start_step)`` so its subsequent masks (and hence
        weights, via the same memoised decode) are bit-identical to
        the original run's stream from that step on. For the sampled
        source this consumes exactly the RNG draws
        ``step_weights``/``weights_lookahead`` would (and advances
        stateful models like the Markov chain); observed sources
        reject it (re-observe instead of replaying RNG)."""
        if rounds < 0:
            raise ValueError("rounds must be >= 0")
        self.mask_source.skip(rounds)
        self.steps_sampled += rounds

    def weights_for(self, alive: np.ndarray, *,
                    method: Optional[str] = None,
                    p: Optional[float] = None) -> np.ndarray:
        """Memoised decode of one given (m,) alive mask -> w float32.

        The mask-agnostic half of ``step_weights``: the observed-mask
        path (heartbeat-derived masks pushed by the driver) and the
        sampled path share this cache, so stagnant failures hit the
        memo whether they were sampled or real. ``method``/``p``
        default to the configured decoding; an adaptive policy passes
        its per-step decision, and the memo key carries (method, p) so
        decisions with different decoders never alias (the debias
        scale applies only to optimal decodes)."""
        alive = np.asarray(alive, dtype=bool)
        if alive.shape != (self.m,):
            raise ValueError(f"mask must be ({self.m},), "
                             f"got {alive.shape}")
        if method is None:
            method = self.coding.decoding
        if p is None:
            p = self.coding.straggler_p
        key = (method, float(p), alive.tobytes())
        w = self._cache.get(key)
        if w is None:
            scale = self.scale if method == "optimal" else 1.0
            w, _ = sw.step_weights(
                self.assignment, alive, method=method, p=p, scale=scale)
            w = w.astype(np.float32)
            if len(self._cache) >= self.cache_size:
                # FIFO eviction: i.i.d. models at large m never repeat
                # masks, and the cache must not grow with step count.
                self._cache.pop(next(iter(self._cache)))
            self._cache[key] = w
            self.decode_calls += 1
        return w

    def _decide(self) -> PolicyDecision:
        """One adaptive decision from the estimator's past-only state
        (the protocol of ``core.adaptive.replay_policy``: decide, use,
        then observe)."""
        decision = self.policy.decide(self.estimator.estimate())
        self.last_decision = decision
        self.decision_counts[decision.method] = \
            self.decision_counts.get(decision.method, 0) + 1
        return decision

    def step_weights(self) -> Tuple[np.ndarray, np.ndarray]:
        """One round from the mask source: returns (w (m,) float32,
        alive (m,) bool). Recorded as a ``coding.step_weights`` span of
        that round, with ``novel`` 1 where the memo missed."""
        calls = self.decode_calls
        with spans.span("coding.step_weights",
                        step=self.steps_sampled) as s:
            alive = self.mask_source.next_mask()
            self.steps_sampled += 1
            if self.policy is not None:
                decision = self._decide()
                w = self.weights_for(alive, method=decision.method,
                                     p=decision.p)
                self.estimator.observe(alive)
            else:
                w = self.weights_for(alive)
            s.set(novel=self.decode_calls - calls)
        return w, alive

    def suggested_lookahead(self) -> int:
        """The policy's current prefetch-horizon suggestion (>= 1);
        1 when no policy is configured. Peeks at the estimate without
        consuming a round."""
        if self.policy is None:
            return 1
        return self.policy.decide(self.estimator.estimate()).lookahead

    def decode_batch(self, masks) -> Tuple[np.ndarray, np.ndarray]:
        """Batched (T, m) masks -> (W, alphas) through the shared
        pipeline -- the lookahead/benchmark path."""
        return sw.batched_step_weights(
            self.assignment, masks, method=self.coding.decoding,
            p=self.coding.straggler_p, scale=self.scale)

    def block_weights(self, w: np.ndarray) -> np.ndarray:
        """Machine weights -> per-block v = A @ w for the dedup step."""
        return sw.block_weights(self.assignment, w).astype(np.float32)

    def weights_lookahead(self, horizon: int
                          ) -> Tuple[np.ndarray, np.ndarray]:
        """Pre-sample the next ``horizon`` rounds and decode them in
        one ``decode_batch`` call: returns (W (horizon, m) float32,
        alive (horizon, m) bool).

        Consumes the same RNG stream as ``step_weights``, one sample
        per round, so a lookahead loop sees bit-identical masks and
        weights to a per-step loop over the same seed (pinned in
        tests/test_coding_runtime.py). The chunk is deduplicated
        against the memo cache first -- under stagnant processes the
        whole horizon is usually a single novel decode (or none).

        With an adaptive policy the rounds inside the chunk decide
        sequentially (decide from the past, decode, observe) through
        the same memoised scalar path as ``step_weights`` -- each
        round's decision may pick a different decoder, so there is no
        single-method batch to dispatch; bit-identity with the
        per-step loop is by construction.

        Recorded as a ``coding.lookahead`` span tagged with the chunk's
        first round, with ``rounds`` (the horizon) and ``novel`` (the
        decodes the memo did not spare).
        """
        if horizon < 1:
            raise ValueError("horizon must be >= 1")
        calls = self.decode_calls
        with spans.span("coding.lookahead", step=self.steps_sampled,
                        rounds=horizon) as s:
            out = self._sample_and_decode(horizon)
            s.set(novel=self.decode_calls - calls)
        return out

    def _sample_and_decode(self, horizon: int
                           ) -> Tuple[np.ndarray, np.ndarray]:
        alive = np.stack(
            [self.mask_source.next_mask() for _ in range(horizon)])
        self.steps_sampled += horizon
        if self.policy is not None:
            rows = []
            for a in alive:
                decision = self._decide()
                rows.append(self.weights_for(a, method=decision.method,
                                             p=decision.p))
                self.estimator.observe(a)
            return np.stack(rows), alive
        keys = [(self.coding.decoding, float(self.coding.straggler_p),
                 a.tobytes()) for a in alive]
        # Gather this horizon's rows locally: FIFO eviction while
        # inserting novel decodes must not drop an entry the horizon
        # itself still references.
        w_by_key = {k: self._cache[k] for k in keys if k in self._cache}
        novel = {}   # mask bytes -> row in the batched decode
        for t, k in enumerate(keys):
            if k not in w_by_key and k not in novel:
                novel[k] = t
        if novel:
            W_new, _ = self.decode_batch(alive[sorted(novel.values())])
            self.decode_calls += len(novel)
            for k, w_new in zip(sorted(novel, key=novel.get), W_new):
                w_by_key[k] = w_new.astype(np.float32)
                if len(self._cache) >= self.cache_size:
                    self._cache.pop(next(iter(self._cache)))
                self._cache[k] = w_by_key[k]
        W = np.stack([w_by_key[k] for k in keys])
        return W, alive


class LookaheadPrefetcher:
    """``weights_lookahead`` off the main thread, bit-identically.

    The train driver's steady-state loop used to stall every
    ``horizon`` steps while ``CodingRuntime.weights_lookahead`` sampled
    and batch-decoded the next chunk on the main thread -- invisible at
    smoke m, a real bubble at very large m where one optimal decode is
    O(m) python. This wrapper runs the same calls on the driver's
    single batch-builder executor, prefetching chunk k+1 while the
    device consumes chunk k.

    Bit-identity with the synchronous path is by construction, not by
    luck: the prefetcher issues the *same* ``weights_lookahead(k)``
    calls in the same order against the same runtime, merely from the
    worker thread, and chunk sizes are capped by the remaining step
    budget exactly like the inline code was -- so RNG consumption,
    memo-cache state, and the (W, alive) stream match the per-step
    loop sample for sample (pinned in tests/test_coding_runtime.py).
    The runtime itself is only ever touched from the worker thread
    after construction; ``block_weights`` (pure, RNG-free) remains
    safe to call from the main thread.
    """

    def __init__(self, runtime: CodingRuntime, pool, horizon: int,
                 total_steps: int):
        if horizon < 1:
            raise ValueError("horizon must be >= 1")
        self.runtime = runtime
        self.pool = pool
        self.horizon = horizon
        self.remaining = total_steps
        self._round = runtime.steps_sampled   # the round next() hands out
        self._chunk = None
        self._cursor = 0
        self._future = self._submit()

    def _submit(self):
        k = min(self.horizon, self.remaining)
        if k < 1:
            return None
        self.remaining -= k
        return self.pool.submit(self.runtime.weights_lookahead, k)

    def next(self) -> Tuple[np.ndarray, np.ndarray]:
        """The next round's (w (m,) float32, alive (m,) bool). Recorded
        as a ``coding.wait`` span of that round: the caller's wait for
        the chunk the worker thread decodes."""
        with spans.span("coding.wait", step=self._round):
            if self._chunk is None or self._cursor == len(self._chunk[0]):
                if self._future is None:
                    raise RuntimeError("lookahead stream exhausted")
                self._chunk = self._future.result()
                self._cursor = 0
                self._future = self._submit()   # prefetch the next chunk
        self._round += 1
        W, alive = self._chunk
        t = self._cursor
        self._cursor += 1
        return W[t], alive[t]
