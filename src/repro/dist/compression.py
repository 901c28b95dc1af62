"""Elastic-deterministic data-parallel gradient exchange with payload
compression, composable with FSDP-sharded optimizer state.

``make_elastic_dp_step`` builds the data-parallel training step used
when gradient all-reduce traffic is the bottleneck (large embedding
tables over slow inter-pod links): the global batch is cut into a fixed
number of **virtual shards** ``V`` (``accum_shards``), each virtual
shard's gradient is compressed (``bf16`` cast or per-tensor symmetric
``int8`` quantisation), and the *compressed* payloads are exchanged
and mean-reduced in a fixed order.  Compression error is carried in
per-virtual-shard **error feedback** state (Seide et al. 2014;
Karimireddy et al. 2019): the residual ``(g + e) - dequant(quant(g +
e))`` is added back to the next step's gradient, so compressed training
converges to the same optimum instead of stalling at the quantisation
floor.

Why virtual shards instead of one shard per device: because ``V`` is
fixed per *run* — not per mesh — the step is **bitwise deterministic
across mesh sizes**.  A run started on 8 devices and resumed on 4
(elastic rescale after a preemption) produces bit-identical parameters
to an uninterrupted run.  Three properties make this hold:

  1. every virtual slice's gradient is computed by a structurally
     identical per-device subgraph: each round processes exactly ONE
     slice per device, and the host drives ``L = V / D`` rounds (fewer
     devices just means more rounds).  Running several slices inside
     one module lets XLA batch the gemms and perturbs the reduction
     order at the ULP level — one-slice-per-dispatch is what pins the
     numerics;
  2. the only cross-device ops are all-gather / all-to-all — exact
     data movement, no arithmetic;
  3. the dequantise / mean / (optional) optimizer update runs in a
     ``combine`` module whose per-element arithmetic never depends on
     the device count: the replicated path reduces one contiguous
     ``[V, ...]`` stack, the fsdp path an explicitly unrolled
     fixed-order sum over the ``V`` contributions of each owned row.

The error-feedback state is likewise ``[V, ...]`` per float leaf —
mesh-shape independent, so a checkpoint restores onto any mesh whose
data-parallel degree divides ``V`` (``repro.ckpt.restore_checkpoint``
re-lays it out; ``repro.train.loop.Trainer`` threads all of this —
driven by a ``repro.train.spec.TrainSpec``, the policy object the
``overlap`` / ``method`` / ``accum_shards`` knobs below are fields of).

Staged round modules (``overlap`` scheduling)
---------------------------------------------
Each round is two separately-jitted stage modules instead of one
monolithic body:

  * ``step.forward_backward(values, batch_rows, rng, rnd)`` — the
    per-slice loss/grad computation.  Its only collectives are the
    scalar loss/aux row gathers; every gradient leaf comes out as a
    per-device ``[D, ...]`` row stack sharded over the data axes, so
    NO payload bytes cross the wire here;
  * ``step.quantise_pack(g_rows, err_rows)`` — error-feedback add,
    quantise, and the payload collective (all-gather, or the fsdp
    ordered-reduce-scatter all-to-all).  This is where the payload
    bytes live.

Because the gradient stays in its producing device's row between the
stages (matching in/out shardings), the split adds no data movement —
and it gives the host scheduler a seam: backward-of-round ``r+1`` can
be dispatched while exchange-of-round ``r`` is still in flight.  The
``overlap`` modes (``repro.train.spec.OVERLAP_MODES``):

  * ``"none"`` — strictly serial rounds; the bit-identity oracle;
  * ``"dispatch"`` — the round-level double buffer: round ``r+1``
    (both stages) is issued while round ``r``'s exchange is in flight,
    blocking on round ``r-1`` to bound the queue to two rounds;
  * ``"backward"`` — additionally issues ``forward_backward(r+1)``
    immediately after ``quantise_pack(r)`` is dispatched, so the
    backward pass of the next round overlaps the current round's
    payload collective (at the cost of keeping two rounds'
    uncompressed gradient stacks live).

All three modes dispatch the SAME two compiled stage executables in
the same per-round order — only the host interleaving differs — so
every mode is bitwise identical to every other, on every mesh whose
dp degree divides ``V``, by construction.  Legacy boolean ``overlap``
values are accepted (``True`` -> "dispatch", ``False`` -> "none").

FSDP composition (``fsdp=True``)
--------------------------------
The plain dp path replicates parameters and all-gathers every round's
full payload stack: ``V x payload`` bytes through every device per
step.  With ``fsdp=True`` each device instead *owns* a ``1/D``
row-slice of every V-divisible float leaf — parameters, both Adam
moments, and the per-round gradient payloads:

  * parameters/moments live row-sharded over the data axes
    (``fsdp_shardings``); a tiny jitted ``step.gather`` module
    all-gathers the parameters ONCE per step for the loss/grad
    computation (the per-round stages then reuse the replicated
    values);
  * the per-round payload collective becomes an **ordered
    reduce-scatter**: ``lax.all_to_all`` delivers each device only the
    D compressed contributions for its owned rows — ``payload`` bytes
    per device per round instead of ``V x payload``.  A *summing*
    reduce-scatter would be cheaper still by a factor of 1 (same wire
    bytes!) but breaks the elasticity contract: the sum's bracketing
    would depend on D, and int8 payloads cannot be de-scaled after a
    blind sum — so we scatter the raw contribution stacks and keep the
    reduction on the owned slice, in fixed virtual-shard order, behind
    an ``optimization_barrier``;
  * ``combine`` runs under ``shard_map``: each device dequantises its
    ``[V, n/D, ...]`` stack, accumulates the V contributions in an
    unrolled fixed order (bitwise independent of the slice width, i.e.
    of D), computes the global grad norm from V-aligned per-segment
    partial sums (exchanged with one tiny ``[V/D]`` all-gather), and
    applies the optimizer update to its owned slice only — no
    replicated update pass.

``step.last_schedule`` records the (fb/issue/drain/consume, round)
dispatch order of the most recent step for the conformance suite
(tests/test_fsdp_exchange.py, tests/test_elastic_train.py).

``payload_bytes`` is the matching accounting hook: bytes of
*compressed* gradient payload a virtual shard ships per step
(quantisation scales — one scalar per tensor — are excluded; they are
noise next to the payload).  The collectives really do carry the
compressed dtype, so the same number is visible in compiled HLO via
``repro.dist.hlo.collective_bytes`` — ``step.collect`` lowers both
stages as one module for exactly that AOT accounting (its collectives
are the union of the two stages'), and the conformance suites pin the
byte totals down.

``make_dp_grad_fn`` is the grads-only surface over the same machinery.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec

from repro.dist import rules as _rules

METHODS = ("none", "bf16", "int8")

# host round-scheduling policies (see the module docstring); the
# canonical home of the policy value is repro.train.spec.TrainSpec,
# which mirrors this tuple without importing jax
OVERLAP_MODES = ("none", "dispatch", "backward")

# bytes per element actually put on the wire.  ``forward_backward``
# casts every gradient (plus its error-feedback row) to f32 before
# compressing, so "none" ships 4 bytes/element regardless of the
# parameter dtype — a bf16 parameter's gradient still crosses the wire
# as f32.
_PAYLOAD_ITEMSIZE = {"none": 4, "bf16": 2, "int8": 1}


def normalise_overlap(overlap) -> str:
    """Map legacy boolean overlap flags onto the mode strings:
    ``True`` was the round-level double buffer, ``False`` the serial
    loop.  ``None`` means "the default" (dispatch)."""
    if overlap is None or overlap is True:
        return "dispatch"
    if overlap is False:
        return "none"
    if overlap not in OVERLAP_MODES:
        raise ValueError(
            f"unknown overlap mode {overlap!r}: expected one of "
            f"{OVERLAP_MODES} (or a legacy bool)")
    return overlap


def _is_float(x) -> bool:
    return jnp.issubdtype(jnp.asarray(x).dtype, jnp.floating)


def _leaf_shape(x):
    return tuple(x.shape) if hasattr(x, "shape") else tuple(jnp.shape(x))


def _leaf_dtype(x):
    dt = getattr(x, "dtype", None)
    return np.asarray(x).dtype if dt is None else dt


def dp_shard_count(mesh) -> int:
    return math.prod(
        mesh.shape[a] for a in _rules.data_mesh_axes(mesh))


def dp_partition_spec(mesh) -> PartitionSpec:
    """Spec sharding a leading axis (virtual-shard rows of the
    error-feedback state, per-round batch rows, fsdp parameter rows)
    over the mesh's data axes — the one rule the Trainer's restore
    path, the dryrun cell builder and the exchange itself all share."""
    dp = _rules.data_mesh_axes(mesh)
    return PartitionSpec(dp if len(dp) > 1 else dp[0])


def fsdp_leaf_sharded(v, n_shards: int) -> bool:
    """Whether ``fsdp=True`` row-shards this leaf over the data axes.

    A float leaf is sharded iff its leading dim is a positive multiple
    of the virtual-shard count ``V`` — a *run* constant, so the
    classification (and therefore the checkpoint layout contract) is
    identical on every mesh an elastic run may resume on, and since
    the dp degree always divides ``V`` a V-divisible dim always splits
    evenly over the devices.  Everything else (codes, scalars, ragged
    leading dims) stays replicated."""
    shape = _leaf_shape(v)
    if not shape or math.prod(shape) == 0:
        return False
    if not jnp.issubdtype(_leaf_dtype(v), jnp.floating):
        return False
    return shape[0] % int(n_shards) == 0


def fsdp_partition_specs(values, mesh, n_shards: int):
    """Per-leaf PartitionSpec tree for the fsdp state layout:
    V-divisible float leaves row-shard over the data axes
    (``dp_partition_spec``), everything else replicates.  Works on
    arrays and ShapeDtypeStructs alike (dryrun cells)."""
    sh = dp_partition_spec(mesh)
    repl = PartitionSpec()
    return jax.tree.map(
        lambda v: sh if fsdp_leaf_sharded(v, n_shards) else repl,
        values)


def fsdp_shardings(values, mesh, n_shards: int):
    """``fsdp_partition_specs`` as a NamedSharding tree — jit
    in/out_shardings, ``device_put`` re-layout, and the elastic
    checkpoint restore all consume this."""
    sh = NamedSharding(mesh, dp_partition_spec(mesh))
    repl = NamedSharding(mesh, PartitionSpec())
    return jax.tree.map(
        lambda v: sh if fsdp_leaf_sharded(v, n_shards) else repl,
        values)


def zeros_error_state(values, n_shards: int):
    """Per-virtual-shard error-feedback state: one residual per float
    leaf, stacked along a leading ``n_shards`` axis (sharded over the
    data axes inside the step).  Row ``v`` belongs to batch slice ``v``
    regardless of the mesh — the state survives an elastic re-mesh."""
    return jax.tree.map(
        lambda v: jnp.zeros((n_shards,) + tuple(jnp.shape(v)),
                            jnp.float32)
        if _is_float(v) else jnp.zeros((n_shards, 0), jnp.float32),
        values)


def payload_bytes(values, method: str) -> int:
    """Compressed gradient bytes one virtual shard ships per step.

    Charged at the **wire** dtype of the exchange, not the parameter
    dtype: the exchange casts every gradient to f32 before compressing,
    so ``method="none"`` is 4 bytes/element even for bf16 parameters
    (the old per-leaf-itemsize accounting under-reported those 2x)."""
    if method not in METHODS:
        raise ValueError(f"unknown compression method {method!r}")
    itemsize = _PAYLOAD_ITEMSIZE[method]
    total = 0
    for v in jax.tree.leaves(values):
        if not _is_float(v):
            continue
        n = int(math.prod(jnp.shape(v))) if jnp.shape(v) else 1
        total += n * itemsize
    return total


def _quantise(t, method: str):
    """t = grad + error (f32) -> (payload, scale, new_error)."""
    if method == "bf16":
        q = t.astype(jnp.bfloat16)
        return q, None, t - q.astype(jnp.float32)
    if method == "int8":
        scale = jnp.maximum(jnp.max(jnp.abs(t)) / 127.0, 1e-30)
        q = jnp.clip(jnp.round(t / scale), -127, 127).astype(jnp.int8)
        return q, scale, t - q.astype(jnp.float32) * scale
    return t, None, jnp.zeros_like(t)                  # none


def _dequantise(stack, scales, method: str):
    """[V, ...] payload stack (+ [V] scales for int8) -> f32 stack."""
    if method == "int8":
        sh = (stack.shape[0],) + (1,) * (stack.ndim - 1)
        return stack.astype(jnp.float32) * scales.reshape(sh)
    return stack.astype(jnp.float32)


def _dp_flat_index(dp_axes, mesh):
    """Row-major flat index over the data axes — matches the
    concatenation order of ``lax.all_gather(axis_name=dp_axes)`` and
    the split/concat order of ``lax.all_to_all``."""
    idx = jnp.zeros((), jnp.int32)
    for a in dp_axes:
        idx = idx * mesh.shape[a] + jax.lax.axis_index(a)
    return idx


def make_elastic_dp_step(loss_fn, mesh, method: str = "none", *,
                         accum_shards: int | None = None,
                         has_aux: bool = False, with_rng: bool = False,
                         apply_fn=None, fsdp: bool = False,
                         overlap="dispatch"):
    """Build the elastic-deterministic data-parallel step.

    ``loss_fn(values, batch[, rng]) -> loss`` (or ``(loss, aux)`` with
    ``has_aux``).  Returns ``step`` with signature::

        step(values, err_state, batch[, rng])            (no apply_fn)
            -> (grads, new_err, loss[, aux])
        step(values, opt_state, err_state, batch[, rng]) (with apply_fn)
            -> (new_values, new_opt, new_err, metrics)

    where ``apply_fn(values, opt_state, grads[, grad_norm=]) ->
    (new_values, new_opt_state, stats)`` and metrics = aux means ∪
    stats ∪ ``{"loss"}``.  Gradients/loss are the fixed-order means
    over the ``accum_shards`` virtual shards — identical bits on any
    mesh whose data-parallel degree divides ``accum_shards``.

    With ``fsdp=True`` the values / optimizer-state trees must be laid
    out per ``fsdp_shardings(values, mesh, accum_shards)``: V-divisible
    float leaves row-sharded over the data axes, everything else
    replicated.  Parameters are all-gathered once per step by the
    jitted ``step.gather`` module, the per-round payload collective is
    an ordered reduce-scatter (``all_to_all`` of the compressed
    contribution stacks — ``payload`` bytes per device per round
    instead of the dp path's ``V x payload`` all-gather), and
    ``apply_fn`` runs on the owned slices only, with the
    bitwise-deterministic global grad norm injected via ``grad_norm=``.
    Returned values / opt state / grads keep the sharded layout.

    ``step`` is a host-level function driving the jitted stage modules
    ``step.forward_backward`` (per-slice loss/grad; scalar gathers
    only) and ``step.quantise_pack`` (error-feedback + compress +
    payload exchange), then ``step.combine`` (dequantise + ordered
    mean + update) and — fsdp only — ``step.gather``.  ``overlap``
    picks the host round schedule (``OVERLAP_MODES``; legacy bools
    accepted): "none" serial, "dispatch" double-buffered rounds,
    "backward" additionally overlapping backward-of-round-``r+1`` with
    exchange-of-round-``r``.  All modes dispatch the same stage
    executables in the same per-round order, so they are bitwise
    identical to each other on every mesh.  ``step.n_shards`` is the
    virtual shard count, ``step.rounds`` the rounds per step on this
    mesh, and ``step.last_schedule`` the (fb/issue/drain/consume,
    round) dispatch trace of the most recent call ("issue" = the
    round's quantise_pack dispatch).  ``step.collect`` traces both
    stages as ONE jitted module with the pre-split calling convention
    ``collect(values, err_rows, batch_rows, rng, rnd)`` — kept for AOT
    collective-byte accounting (its collectives are the union of the
    stages'); the whole of ``step`` is likewise jax-traceable, so it
    can be lowered as one module (launch/dryrun.py).
    """
    if method not in METHODS:
        raise ValueError(f"unknown compression method {method!r}")
    overlap = normalise_overlap(overlap)
    dp = _rules.data_mesh_axes(mesh)
    D = dp_shard_count(mesh)
    V = D if accum_shards is None else int(accum_shards)
    if V % D != 0:
        raise ValueError(
            f"accum_shards={V} must be a multiple of the mesh's "
            f"data-parallel degree {D}")
    L = V // D
    vg = jax.value_and_grad(loss_fn, has_aux=has_aux, allow_int=True)

    repl = PartitionSpec()
    err_spec = dp_partition_spec(mesh)

    def _sharded(v) -> bool:
        return fsdp and fsdp_leaf_sharded(v, V)

    def _gath(x):
        return jax.lax.all_gather(x, dp, axis=0, tiled=False)

    def _stack_v(xs):
        # interleave the L rounds back into virtual order v = d*L + r:
        # stack [L × [D, ...]] on axis=1 -> [D, L, ...] -> [V, ...].
        # The barrier materialises the [V, ...] stack before any
        # reduction: XLA otherwise fuses the concatenate into the mean
        # and re-brackets the sum differently per round count — the
        # reduction must always see one contiguous [V, ...] operand for
        # the fixed-order (mesh-size-independent) mean to hold bitwise.
        s = jnp.stack(xs, axis=1)
        return jax.lax.optimization_barrier(
            s.reshape((V,) + s.shape[2:]))

    # ---------------------------------------------------- stage bodies
    # Stage 1: per-slice forward + backward.  One virtual slice per
    # device; gradient leaves leave the module as [1, ...] local rows
    # (global [D, ...], row-sharded over the data axes) so the only
    # wire traffic is the scalar loss/aux gathers.  Non-float / float0
    # / empty leaves become [1, 0] f32 placeholders — float0 cannot
    # cross a jit boundary, and quantise_pack re-detects them by shape.
    def fb_body(values, batch_rows, rng, rnd):
        mb = jax.tree.map(lambda x: x[0], batch_rows)
        vi = _dp_flat_index(dp, mesh) * L + rnd        # virtual index
        args = (values, mb)
        if with_rng:
            args += (jax.random.fold_in(rng, vi),)
        out, g = vg(*args)
        loss, aux = out if has_aux else (out, {})

        def one_g(gl):
            if not _is_float(gl) or not gl.size:
                return jnp.zeros((1, 0), jnp.float32)
            return gl.astype(jnp.float32)[None]

        flat_g, tdef = jax.tree.flatten(g)
        g_rows = tdef.unflatten([one_g(gl) for gl in flat_g])
        return g_rows, _gath(loss), jax.tree.map(_gath, dict(aux))

    # Stage 2: error-feedback add + quantise + the payload collective.
    # Consumes the [D, ...] row stacks sharded exactly as stage 1
    # produced them, so the jit boundary moves no data.
    def qp_body(g_rows, err_rows):
        def one(gr, el):
            if gr.shape[1:] == (0,):
                # int/float0/empty leaves: nothing to exchange
                z = jnp.zeros((0,), jnp.float32)
                return _gath(z), jnp.zeros((), jnp.float32), el
            t = gr[0] + el[0]
            pay, scale, new_e = _quantise(t, method)
            if scale is None:
                scale = jnp.zeros((), jnp.float32)
            if _sharded(gr[0]):
                # ordered reduce-scatter: every device contributes its
                # full compressed slice gradient and receives only the
                # D contributions for its OWN rows (concatenated in
                # source-device order, i.e. contribution-major) —
                # `payload` wire bytes per device instead of the
                # all-gather's V x payload, with no pre-reduction that
                # would tie the arithmetic to the mesh size.
                payx = jax.lax.all_to_all(pay, dp, split_axis=0,
                                          concat_axis=0, tiled=True)
            else:
                payx = _gath(pay)
            return payx, scale, new_e[None]

        flat_g, tdef = jax.tree.flatten(g_rows)
        flat_e = tdef.flatten_up_to(err_rows)
        outs = [one(gl, el) for gl, el in zip(flat_g, flat_e)]
        pays = tdef.unflatten([o[0] for o in outs])    # [D, ...] | [n]
        scales = tdef.unflatten([_gath(o[1]) for o in outs])  # [D]
        new_err = tdef.unflatten([o[2] for o in outs])
        return pays, scales, new_err

    # ------------------------------------------------- stage wrappers
    def _specs_for(values, err_rows, batch_rows):
        specs_v = jax.tree.map(lambda _: repl, values)
        specs_g = jax.tree.map(lambda _: err_spec, values)
        specs_e = jax.tree.map(lambda _: err_spec, err_rows)
        specs_b = jax.tree.map(lambda _: err_spec, batch_rows)
        # scattered payloads come out row-sharded; gathered ones (and
        # every non-fsdp payload) replicated
        pay_specs = jax.tree.map(
            lambda v: err_spec if _sharded(v) else repl, values)
        return specs_v, specs_g, specs_e, specs_b, pay_specs

    def fb(values, batch_rows, rng, rnd):
        specs_v = jax.tree.map(lambda _: repl, values)
        specs_g = jax.tree.map(lambda _: err_spec, values)
        specs_b = jax.tree.map(lambda _: err_spec, batch_rows)
        f = shard_map(
            fb_body, mesh=mesh,
            in_specs=(specs_v, specs_b, repl, repl),
            out_specs=(specs_g, repl, repl),
            check_vma=False)
        return f(values, batch_rows, rng, rnd)

    def qp(g_rows, err_rows):
        specs_g = jax.tree.map(lambda _: err_spec, g_rows)
        specs_e = jax.tree.map(lambda _: err_spec, err_rows)
        pay_specs = jax.tree.map(
            lambda g: err_spec if _sharded_rows(g) else repl, g_rows)
        f = shard_map(
            qp_body, mesh=mesh,
            in_specs=(specs_g, specs_e),
            out_specs=(pay_specs,
                       jax.tree.map(lambda _: repl, g_rows),
                       specs_e),
            check_vma=False)
        return f(g_rows, err_rows)

    def _sharded_rows(g) -> bool:
        # g is the [D, ...] row stack of a leaf; the leaf's own shape
        # is g.shape[1:], which is what the fsdp classification reads
        shape = _leaf_shape(g)[1:]
        if not shape or math.prod(shape) == 0:
            return False
        return fsdp and (shape[0] % V == 0) and \
            jnp.issubdtype(_leaf_dtype(g), jnp.floating)

    forward_backward = jax.jit(fb)
    quantise_pack = jax.jit(qp)

    def collect(values, err_rows, batch_rows, rng, rnd):
        # both stages traced as ONE module — the AOT accounting
        # surface (pre-split calling convention); the scheduler below
        # never dispatches this, it drives the stage jits directly
        g_rows, loss_g, aux_g = fb(values, batch_rows, rng, rnd)
        pays, scales, new_err = qp(g_rows, err_rows)
        return pays, scales, new_err, loss_g, aux_g

    collect = jax.jit(collect)

    if fsdp:
        # one parameter all-gather per step (not per round): a jitted
        # identity whose output sharding is "replicated" — lowered to
        # the all-gathers visible in step.gather's HLO
        gather = jax.jit(lambda values: values,
                         out_shardings=NamedSharding(mesh, repl))
    else:
        gather = None

    def combine_dp(values, opt_state, pays, scales, losses, auxes):
        flat_p = [jax.tree.leaves(p) for p in pays]
        flat_s = [jax.tree.leaves(s) for s in scales]
        tdef = jax.tree.structure(pays[0])
        flat_v = tdef.flatten_up_to(values)
        grads = []
        for li in range(len(flat_p[0])):
            rounds_p = [flat_p[r][li] for r in range(L)]
            if rounds_p[0].shape[1:] == (0,):
                # unexchanged (int/empty) leaf: a zero gradient in the
                # leaf's own shape/dtype keeps tree-wide updates valid
                vl = flat_v[li]
                grads.append(jnp.zeros(jnp.shape(vl),
                                       jnp.asarray(vl).dtype))
                continue
            pstack = _stack_v(rounds_p)                # [V, ...]
            sstack = _stack_v([flat_s[r][li] for r in range(L)])
            deq = _dequantise(pstack, sstack, method)
            grads.append(jnp.mean(deq, axis=0))        # fixed order
        grads = tdef.unflatten(grads)
        loss = jnp.mean(_stack_v(list(losses)))
        aux = jax.tree.map(lambda *xs: jnp.mean(_stack_v(list(xs))),
                           *auxes) if auxes[0] else {}
        if apply_fn is None:
            return grads, loss, aux
        new_values, new_opt, stats = apply_fn(values, opt_state, grads)
        mets = {"loss": loss, **aux, **stats}
        return new_values, new_opt, mets

    def combine_fsdp(values, opt_state, pays, scales, losses, auxes):
        flat_v, tdef = jax.tree.flatten(values)
        # classified on the GLOBAL shapes (inside the shard_map body
        # only local slices are visible)
        flags = [_sharded(v) for v in flat_v]
        v_specs = tdef.unflatten(
            [err_spec if f else repl for f in flags])
        o_specs = (jax.tree.map(
            lambda x: err_spec if _sharded(x) else repl, opt_state)
            if opt_state is not None else repl)

        def body_c(values_l, opt_l, pays_l, scales_l, losses_l,
                   auxes_l):
            flat_vl = tdef.flatten_up_to(values_l)
            flat_p = [tdef.flatten_up_to(p) for p in pays_l]
            flat_s = [tdef.flatten_up_to(s) for s in scales_l]
            grads = []
            sq_terms = []
            for li in range(len(flat_vl)):
                rounds_p = [flat_p[r][li] for r in range(L)]
                if rounds_p[0].shape[1:] == (0,):
                    vl = flat_vl[li]
                    grads.append(jnp.zeros(jnp.shape(vl),
                                           jnp.asarray(vl).dtype))
                    continue
                sstack = _stack_v([flat_s[r][li] for r in range(L)])
                if flags[li]:
                    # each round's local payload is the contribution
                    # stack for the owned rows, contribution-major:
                    # [D * n/D, ...] -> [D, n/D, ...]; interleaving the
                    # L rounds on axis=1 restores virtual order
                    xs = [p.reshape((D, p.shape[0] // D) + p.shape[1:])
                          for p in rounds_p]
                    s = jnp.stack(xs, axis=1)      # [D, L, n/D, ...]
                    pstack = jax.lax.optimization_barrier(
                        s.reshape((V,) + s.shape[2:]))
                else:
                    pstack = _stack_v(rounds_p)
                deq = _dequantise(pstack, sstack, method)
                if flags[li]:
                    # the owned-slice width n/D varies with the mesh, so
                    # a reduce over axis 0 is not guaranteed to keep its
                    # bracketing across D; an unrolled elementwise chain
                    # over the V contributions is, by construction
                    acc = deq[0]
                    for vv in range(1, V):
                        acc = acc + deq[vv]
                    g = acc / jnp.float32(V)
                else:
                    g = jnp.mean(deq, axis=0)
                grads.append(g)
                if flags[li]:
                    # global grad norm from V-aligned segments: segment
                    # s covers rows [s*n/V, (s+1)*n/V) of the full leaf
                    # on every mesh, so each partial sum reduces an
                    # identically-shaped operand regardless of D
                    nseg = V // D
                    slen = g.shape[0] // nseg
                    segs = [jnp.sum(jnp.square(
                        jax.lax.optimization_barrier(
                            g[i * slen:(i + 1) * slen])))
                        for i in range(nseg)]
                    seg_all = jax.lax.all_gather(
                        jnp.stack(segs), dp, axis=0, tiled=True)  # [V]
                    sq_terms.append(jnp.sum(
                        jax.lax.optimization_barrier(seg_all)))
                else:
                    sq_terms.append(jnp.sum(jnp.square(g)))
            grads_t = tdef.unflatten(grads)
            loss = jnp.mean(_stack_v(list(losses_l)))
            aux = jax.tree.map(
                lambda *xs: jnp.mean(_stack_v(list(xs))),
                *auxes_l) if auxes_l[0] else {}
            if apply_fn is None:
                return grads_t, loss, aux
            gn = (jnp.sqrt(sum(sq_terms)) if sq_terms
                  else jnp.zeros((), jnp.float32))
            new_values, new_opt, stats = apply_fn(
                values_l, opt_l, grads_t, grad_norm=gn)
            mets = {"loss": loss, **aux, **stats}
            return new_values, new_opt, mets

        pay_specs = tuple(v_specs for _ in range(L))
        if apply_fn is None:
            out_specs = (v_specs, repl, repl)
        else:
            out_specs = (v_specs, o_specs, repl)
        f = shard_map(
            body_c, mesh=mesh,
            in_specs=(v_specs, o_specs, pay_specs, repl, repl, repl),
            out_specs=out_specs, check_vma=False)
        return f(values, opt_state, pays, scales, losses, auxes)

    combine = jax.jit(combine_fsdp if fsdp else combine_dp)

    idx_rounds = [np.arange(D) * L + r for r in range(L)]

    def _block(tree):
        # backpressure for the double buffer; a no-op while the whole
        # step is being traced as one module (dryrun AOT accounting)
        leaves = jax.tree.leaves(tree)
        if leaves and not isinstance(leaves[0], jax.core.Tracer):
            jax.block_until_ready(tree)

    def _run(values, opt_state, err_state, batch, rng):
        bshape = {jnp.shape(x)[0] for x in jax.tree.leaves(batch)}
        for b in bshape:
            if b % V != 0:
                raise ValueError(
                    f"batch leading dim {b} not divisible by "
                    f"accum_shards={V}")
        rows = jax.tree.map(
            lambda x: x.reshape((V, jnp.shape(x)[0] // V)
                                + jnp.shape(x)[1:]), batch)
        values_full = gather(values) if fsdp else values
        pays, scales, errs, losses, auxes = [], [], [], [], []
        schedule = []
        fb_outs = [None] * L

        def issue_fb(r):
            b_r = jax.tree.map(lambda x: x[idx_rounds[r]], rows)
            schedule.append(("fb", r))
            fb_outs[r] = forward_backward(values_full, b_r, rng,
                                          jnp.int32(r))

        def issue_qp(r):
            e_r = jax.tree.map(lambda x: x[idx_rounds[r]], err_state)
            schedule.append(("issue", r))
            return quantise_pack(fb_outs[r][0], e_r)

        def consume(r, q):
            p, s, e = q
            schedule.append(("consume", r))
            pays.append(p)
            scales.append(s)
            errs.append(e)
            losses.append(fb_outs[r][1])
            auxes.append(fb_outs[r][2])
            fb_outs[r] = None     # drop the uncompressed grad stack

        if overlap == "dispatch":
            # round-level double buffer: round r+1 (both stages) is
            # issued while round r's exchange is still in flight;
            # blocking on round r-1 bounds the in-flight window to two
            # rounds without ever serialising a dispatch against the
            # previous execution
            def issue(r):
                issue_fb(r)
                return issue_qp(r)
            pending, prev = issue(0), None
            for r in range(L):
                nxt = issue(r + 1) if r + 1 < L else None
                if prev is not None:
                    _block(prev[0])
                    schedule.append(("drain", r - 1))
                consume(r, pending)
                prev, pending = pending, nxt
        elif overlap == "backward":
            # backward-of-round-r+1 overlaps exchange-of-round-r: the
            # forward_backward(r+1) dispatch lands between issuing
            # quantise_pack(r) and consuming round r, on top of the
            # dispatch double buffer (block on r-1 only).  Costs one
            # extra live uncompressed gradient stack.
            issue_fb(0)
            prev = None
            for r in range(L):
                q = issue_qp(r)
                if r + 1 < L:
                    issue_fb(r + 1)
                if prev is not None:
                    _block(prev[0])
                    schedule.append(("drain", r - 1))
                consume(r, q)
                prev = q
        else:                                          # "none": serial
            for r in range(L):
                issue_fb(r)
                consume(r, issue_qp(r))
        step.last_schedule = tuple(schedule)
        # err rows back into [V, ...] virtual order (exact interleave)
        new_err = jax.tree.map(
            lambda *xs: jnp.stack(xs, axis=1).reshape(
                (V,) + jnp.shape(xs[0])[1:]), *errs)
        out = combine(values, opt_state, tuple(pays), tuple(scales),
                      tuple(losses), tuple(auxes))
        if apply_fn is None:
            grads, loss, aux = out
            ret = (grads, new_err, loss)
            return ret + ((aux,) if has_aux else ())
        new_values, new_opt, mets = out
        return new_values, new_opt, new_err, mets

    if apply_fn is None:
        if with_rng:
            def step(values, err_state, batch, rng):
                return _run(values, None, err_state, batch, rng)
        else:
            def step(values, err_state, batch):
                return _run(values, None, err_state, batch, None)
    else:
        if with_rng:
            def step(values, opt_state, err_state, batch, rng):
                return _run(values, opt_state, err_state, batch, rng)
        else:
            def step(values, opt_state, err_state, batch):
                return _run(values, opt_state, err_state, batch, None)

    step.n_shards = V
    step.rounds = L
    step.method = method
    step.fsdp = fsdp
    step.overlap = overlap
    step.forward_backward = forward_backward
    step.quantise_pack = quantise_pack
    step.collect = collect
    step.combine = combine
    step.gather = gather
    step.last_schedule = ()
    return step


def make_dp_grad_fn(loss_fn, mesh, method: str = "none", *,
                    accum_shards: int | None = None,
                    fsdp: bool = False, overlap="dispatch"):
    """Grads-only surface: ``(values, err_state, batch) -> (grads,
    err_state, loss)``.  ``loss_fn(values, batch) -> scalar``; the
    batch's leading dim is split over ``accum_shards`` virtual shards
    (default: the mesh's data-parallel degree) and grads/loss are the
    fixed-order across-shard means — identical semantics to an
    uncompressed all-reduce when ``method="none"``, identical *bits*
    across mesh sizes for every method.  Non-float leaves (frozen
    codebooks etc.) come back as zero "gradients" in the leaf's own
    shape/dtype, so tree-wide ``v - lr * g`` updates stay valid.  With
    ``fsdp=True`` values must be laid out per ``fsdp_shardings`` and
    the returned grads keep that sharded layout.  ``overlap`` is an
    ``OVERLAP_MODES`` string (legacy bools accepted)."""
    return make_elastic_dp_step(loss_fn, mesh, method,
                                accum_shards=accum_shards, fsdp=fsdp,
                                overlap=overlap)
