"""Device-side witness extraction over the compiled bucket schedules.

This is the lowering half of :mod:`repro.witness`: a second kernel family
next to the counting kernels, built over the SAME padded compare cubes.
Where a counting kernel reduces the cube to a per-seed scalar, the
witness kernel keeps the cube's *flat candidate order* and selects the
first ``k`` matching candidates per seed:

1. broadcast the emit count cube against every frontier mask to the full
   query shape ``(B, A1..Ak, DA, DB)`` and flatten to ``(B, C)``;
2. ``cumsum`` along the candidate axis — candidate ranks are now a
   prefix-sum coordinate system;
3. for ranks ``0..k-1``, a vmapped ``searchsorted(cumsum, rank, right)``
   finds the cube slot holding that rank, and ``within = rank - prefix``
   indexes *inside* the slot's count (counting primitives never
   materialize their runs: the j-th matched edge of a run that starts at
   flat row position ``p`` sits at ``p + j`` — see the ``*_pos`` variants
   in :mod:`repro.core.ops`);
4. flat row positions become edge ids through the row-order eid arrays
   (``out_eid``/``in_eid`` for id-sorted rows, ``out_eid_t``/``in_eid_t``
   for time-sorted rows) carried by :class:`repro.graph.csr.DeviceGraph`.

Hub-tail sweep grids stay fused in-kernel: each offset combination's
top-k candidates carry per-axis GLOBAL coordinates (slot index plus
sweep offset) as sort keys, and a ``lax.fori_loop`` merges combos with a
multi-operand ``jax.lax.sort`` — so the selection order is independent
of the sweep decomposition, and a swept bucket is still ONE launch.

Witness schedules (``schedule_for(..., witness=True)``) keep the
counting path's per-branch hub decomposition: a seed whose level-1
frontier is too costly to pad as one row is split host-side into one row
per level-1 branch item, so a hub on that frontier costs its own degree
rather than the seed's whole padded cube.  Bulk rows scatter their packed
ids straight into their seed's slot (one row per seed, so ``.at[seg].set``
is race-free); branch rows scatter into one slot per ITEM, and the host
merge concatenates a seed's items in item order — the level-1 axis's
canonical order — and keeps the first k.  The ``bs2`` strategy is
remapped to ``bs1`` (bs2 enumerates the fixed side outermost — a
different candidate order), and the pairwise compare cube always takes
the XLA broadcast path (the Pallas ``intersect_count`` op returns reduced
counts, not positions).

Execution mirrors :func:`repro.core.executor.execute` with device
accumulators for per-seed counts (scatter-add) and packed ``(B, k, H)``
witness ids (scatter-set), plus the per-item pair of them for branch
rows; the mine's single host sync fetches all of them in one
``jax.device_get``.
"""
from __future__ import annotations

from typing import Callable, Dict, List, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import executor, ops
from repro.core.compiler import _I32_MAX, INVALID, _graph_rows
from repro.core.spec import NEG_INF, POS_INF, Neigh, NodeRef, SetExpr, Stage, StageT, TimeBound, _SeedT
from repro.graph.csr import DeviceGraph
from repro.witness import Witnesses, witness_layout

__all__ = ["mine_witnesses"]


def _build_witness_kernel(
    ir,
    n_iters: int,
    strat: int,
    dims: Tuple[int, ...],
    sweeps: Tuple[int, ...],
    kp: int,
    branch: bool,
) -> Callable:
    """Lower the stage graph to one jitted top-k witness kernel for a
    fixed (strategy, bucket widths, sweep grid, k-capacity, branch mode)
    combination.  In branch mode each row is one hub branch item whose
    level-1 node and time arrive as ``fr``/``frt``; its level-1 hop comes
    back as ``-1`` and the host merge fills in the item's edge id.

    Returns ``kernel(dg, s, d, st_, fr, frt) -> (counts (B,), eids
    (B, kp, H))`` — counts are the exact per-row instance counts (same
    reduction as the counting kernel), eids the first ``kp`` candidate
    hop tuples in canonical cube order (``-1`` past the count and at
    union placeholder hops).  Binds only plain values (never ``self``):
    the kernels cache outlives the compiled plan.
    """
    layout = witness_layout(ir)  # raises NotImplementedError for excluded shapes
    H = len(layout)
    k = len(ir.frontiers)
    if not sweeps:
        sweeps = (1,) * len(dims)
    if strat == 1:
        raise AssertionError("witness schedules remap bs2 to bs1")
    n_axes = len(dims)  # k + 2: frontier levels + both intersect expansions
    # actual cube axis sizes: a union frontier concatenates both sides
    # before dedup, so its axis is twice the scheduled bucket width
    union_lvls = {
        i + 1
        for i, f in enumerate(ir.frontiers)
        if isinstance(f.operand, SetExpr) and f.operand.op == "union"
    }
    adims = tuple(
        (2 * w if (j + 1) in union_lvls else w) for j, w in enumerate(dims)
    )
    C = int(np.prod(adims, dtype=np.int64))
    ranks = jnp.arange(kp, dtype=jnp.int32)

    def lift(arr, lvl):
        arr = jnp.asarray(arr)
        while arr.ndim < lvl + 1:
            arr = arr[..., None]
        return arr

    def mid_lift(arr, axis_lvl):
        a = jnp.asarray(arr)
        return a.reshape(a.shape[0], *([1] * (axis_lvl - 1)), a.shape[1])

    def _eid_rows(dg: DeviceGraph, direction: str, sorted_by: str):
        if direction == "out":
            return dg.out_eid if sorted_by == "id" else dg.out_eid_t
        return dg.in_eid if sorted_by == "id" else dg.in_eid_t

    def body(dg: DeviceGraph, s, d, st_, fr, frt, offs):
        B = s.shape[0]
        node_env = {"seed.src": (s, 0), "seed.dst": (d, 0)}
        time_env: Dict[str, Tuple] = {}
        mask_env: Dict[str, Tuple] = {}

        def bound_at(tb: TimeBound, lvl: int):
            if tb.anchor is None:
                return jnp.int32(tb.offset)
            if isinstance(tb.anchor, _SeedT):
                base = st_
            else:
                base = time_env[tb.anchor.name][0]
            return lift(base + jnp.int32(tb.offset), lvl)

        def node_at(ref: NodeRef, lvl: int):
            arr, _ = node_env[ref.name]
            return lift(arr, lvl)

        # ---- frontier chain (counting-kernel order, positions kept) ---
        # frontier_hops[lvl-1] = (pos cube, eid rows) or None for unions
        frontier_hops: List[Optional[Tuple]] = []
        start_level = 1
        if branch:
            f1 = ir.frontiers[0]
            bmask = (fr >= 0)[:, None]
            node_env[f1.name] = (jnp.where(bmask, fr[:, None], -1), 1)
            time_env[f1.name] = (frt[:, None], 1)
            mask_env[f1.name] = (bmask, 1)
            frontier_hops.append(None)  # the item's edge, filled host-side
            start_level = 2
        for lvl in range(start_level, k + 1):
            fa = ir.frontiers[lvl - 1]
            width = dims[lvl - 1]
            off = offs[lvl - 1]
            opn = fa.operand
            a1 = bound_at(fa.window.after, lvl)
            u1 = bound_at(fa.window.until, lvl)

            def expand_side(nb: Neigh, _w=width, _off=off, _lvl=lvl):
                indptr, nbr, t, _ = _graph_rows(dg, nb.direction)
                base, _ = node_env[nb.node.name]
                return ops.expand_pos(
                    indptr, (nbr, t), lift(base, _lvl - 1), _w, offset=_off
                )

            def filt(mask, ids, ts, _fa=fa, _a1=a1, _u1=u1, _lvl=lvl):
                m = mask & (ts > _a1) & (ts <= _u1)
                for ref in _fa.skip_eq:
                    m = m & (ids != node_at(ref, _lvl))
                return m

            if isinstance(opn, SetExpr) and opn.op == "union":
                m1, _, i1, t1 = expand_side(opn.left)
                m2, _, i2, t2 = expand_side(opn.right)
                m1, m2 = filt(m1, i1, t1), filt(m2, i2, t2)
                ids = jnp.concatenate([i1, i2], axis=-1)
                ts = jnp.concatenate([t1, t2], axis=-1)
                mask = jnp.concatenate([m1, m2], axis=-1)
                ids, ts, mask = ops.dedup_ids(ids, ts, mask, INVALID)
                frontier_hops.append(None)  # node set: no canonical edge
            elif isinstance(opn, SetExpr) and opn.op == "difference":
                mask, pos, ids, ts = expand_side(opn.left)
                mask = filt(mask, ids, ts)
                rb = opn.right
                indptr_r, nbr_r, t_r, _ = _graph_rows(dg, rb.direction)
                member = ops.count_id_in_window(
                    nbr_r,
                    t_r,
                    indptr_r,
                    node_at(rb.node, lvl),
                    jnp.where(mask, ids, -1),
                    NEG_INF,
                    POS_INF,
                    n_iters,
                )
                mask = mask & (member == 0)
                frontier_hops.append(
                    (pos, _eid_rows(dg, opn.left.direction, "id"))
                )
            else:
                mask, pos, ids, ts = expand_side(opn)
                mask = filt(mask, ids, ts)
                frontier_hops.append((pos, _eid_rows(dg, opn.direction, "id")))
            ids = jnp.where(mask, ids, -1)
            node_env[fa.name] = (ids, lvl)
            time_env[fa.name] = (ts, lvl)
            mask_env[fa.name] = (mask, lvl)

        # ---- emit lowering with run positions -------------------------
        def win_level(st: Stage) -> int:
            lvl = 0
            for b in (st.window.after, st.window.until):
                if isinstance(b.anchor, StageT):
                    lvl = max(lvl, ir.nodes[b.anchor.name].level)
            return lvl

        def eval_count(st: Stage):
            """(count cube, emit hop descriptors) for a count stage."""
            if st.op == "count_window":
                nb = st.operand
                base, lvl = node_env[nb.node.name]
                lvl = max(lvl, win_level(st))
                indptr, _, _, t_sorted = _graph_rows(dg, nb.direction)
                cnt, start = ops.count_window_pos(
                    t_sorted,
                    indptr,
                    lift(base, lvl),
                    bound_at(st.window.after, lvl),
                    bound_at(st.window.until, lvl),
                    n_iters,
                )
                return cnt, [("run", start, _eid_rows(dg, nb.direction, "time"))]
            if st.op == "count_edges":
                base, lvl_s = node_env[st.edge_src.name]
                dst_arr, lvl_d = node_env[st.edge_dst.name]
                lvl = max(lvl_s, lvl_d, win_level(st))
                if st is ir.ce_pw and strat == 2:
                    # pairwise witness lowering: the fixed-side expansion
                    # owns axis k+2 (dims slot k+1) so the cube layout
                    # matches (W1..Wk, DA=1, DB) — the counting kernel's
                    # axis-(k+1) placement reduces to the same counts but
                    # would scramble the slot -> coordinate decomposition
                    d_b, off_b = dims[k + 1], offs[k + 1]
                    la = k + 2
                    indptr_i, nbr_i, t_i, _ = _graph_rows(dg, "in")
                    m3, pos_y, y_ids, y_t = ops.expand_pos(
                        indptr_i, (nbr_i, t_i), dst_arr, d_b, offset=off_b
                    )
                    y2, yt2 = mid_lift(y_ids, la), mid_lift(y_t, la)
                    aw = bound_at(st.window.after, la)
                    uw = bound_at(st.window.until, la)
                    pair = (
                        mid_lift(m3, la)
                        & (lift(base, la) == y2)
                        & (yt2 > aw)
                        & (yt2 <= uw)
                    )
                    return pair.astype(jnp.int32), [
                        ("pos", mid_lift(pos_y, la), dg.in_eid)
                    ]
                indptr, nbr, t, _ = _graph_rows(dg, "out")
                cnt, start = ops.count_id_in_window_pos(
                    nbr,
                    t,
                    indptr,
                    lift(base, lvl),
                    lift(dst_arr, lvl),
                    bound_at(st.window.after, lvl),
                    bound_at(st.window.until, lvl),
                    n_iters,
                )
                return cnt, [("run", start, dg.out_eid)]
            if st.op == "product":
                f1_, f2_ = st.factors
                c1, h1 = eval_count(ir.nodes[f1_].stage)
                c2, h2 = eval_count(ir.nodes[f2_].stage)
                if c1.ndim != 1 or c2.ndim != 1:
                    raise NotImplementedError("witness product of scalar counts only")
                # within in [0, c1*c2): factor 1 outer, factor 2 inner
                return c1 * c2, [("prod", h1[0], h2[0], c2)]
            raise NotImplementedError(f"witness emit op {st.op!r}")

        emit = ir.emit
        ehops: List[Tuple] = []
        if emit.op == "for_all":
            cnt = jnp.ones((B,), jnp.int32)  # masks supply everything
        elif emit.op == "intersect":
            it = emit
            a, b = it.operands
            d_a, d_b = dims[k], dims[k + 1]
            off_a, off_b = offs[k], offs[k + 1]
            fr_ids = lift(node_env[a.node.name][0], k)
            indptr_a, nbr_a, t_a, _ = _graph_rows(dg, a.direction)
            indptr_b, nbr_b, t_b, _ = _graph_rows(dg, b.direction)
            fixed = node_env[b.node.name][0]
            lx = k + 1
            ea = _eid_rows(dg, a.direction, "id")
            eb = _eid_rows(dg, b.direction, "id")
            m2, pos_x, x_ids, x_t = ops.expand_pos(
                indptr_a, (nbr_a, t_a), fr_ids, d_a, offset=off_a
            )
            a1 = bound_at(it.window.after, lx)
            u1 = bound_at(it.window.until, lx)
            m_x = m2 & (x_t > a1) & (x_t <= u1)
            for ref in it.skip_eq:
                m_x = m_x & (x_ids != node_at(ref, lx))
            if strat == 0:  # bs1: y run addressed inside the fixed row
                a2 = bound_at(it.window2.after, lx)
                u2 = bound_at(it.window2.until, lx)
                aa2 = jnp.maximum(a2, x_t) if it.ordered else a2
                cnt, ystart = ops.count_id_in_window_pos(
                    nbr_b,
                    t_b,
                    indptr_b,
                    lift(fixed, lx),
                    jnp.where(m_x, x_ids, -1),
                    aa2,
                    u2,
                    n_iters,
                )
                cnt = jnp.where(m_x, cnt, 0)
                ehops = [("pos", pos_x, ea), ("run", ystart, eb)]
            else:  # pw compare cube — XLA broadcast path (positions kept)
                m3, pos_y, y_ids, y_t = ops.expand_pos(
                    indptr_b, (nbr_b, t_b), fixed, d_b, offset=off_b
                )
                ly = lx + 1
                yb, yt = mid_lift(y_ids, ly), mid_lift(y_t, ly)
                a2 = bound_at(it.window2.after, ly)
                u2 = bound_at(it.window2.until, ly)
                pair = (
                    m_x[..., None]
                    & mid_lift(m3, ly)
                    & (x_ids[..., None] == yb)
                    & (yt > a2)
                    & (yt <= u2)
                )
                if it.ordered:
                    pair = pair & (yt > x_t[..., None])
                cnt = pair.astype(jnp.int32)
                ehops = [("pos", pos_x, ea), ("pos", mid_lift(pos_y, ly), eb)]
        else:
            cnt, ehops = eval_count(emit)

        # ---- top-k selection over the full candidate cube -------------
        cube = lift(cnt.astype(jnp.int32), n_axes)
        for f in ir.frontiers:
            cube = cube * lift(mask_env[f.name][0], n_axes).astype(jnp.int32)
        flat = jnp.broadcast_to(cube, (B,) + adims).reshape(B, C)
        ccum = jnp.cumsum(flat, axis=1)
        total = ccum[:, -1]
        slot = jax.vmap(
            lambda cc: jnp.searchsorted(cc, ranks, side="right")
        )(ccum)
        slot = jnp.minimum(slot, C - 1).astype(jnp.int32)
        prefix = jnp.take_along_axis(ccum, slot, axis=1) - jnp.take_along_axis(
            flat, slot, axis=1
        )
        within = ranks[None, :] - prefix
        valid = ranks[None, :] < total[:, None]

        def at_slot(cube_):
            x = jnp.broadcast_to(lift(cube_, n_axes), (B,) + adims)
            return jnp.take_along_axis(x.reshape(B, C), slot, axis=1)

        def eid_at(pos_plane, earr):
            cap = earr.shape[0] - 1
            return jnp.where(valid, earr[jnp.clip(pos_plane, 0, cap)], -1)

        # sort keys: per-axis GLOBAL cube coordinates (slot decomposition
        # plus the sweep offset) and the within-slot rank — row-major
        # lexicographic order over these keys IS the canonical candidate
        # order, and coordinate tuples are unique across sweep combos
        keys = []
        for j in range(n_axes):
            stride = int(np.prod(adims[j + 1 :], dtype=np.int64)) or 1
            i_j = (slot // stride) % adims[j]
            keys.append(jnp.where(valid, i_j + offs[j], _I32_MAX))
        keys.append(jnp.where(valid, within, _I32_MAX))

        planes = []
        for fh in frontier_hops:
            if fh is None:
                planes.append(jnp.full((B, kp), -1, jnp.int32))
            else:
                pos_cube, earr = fh
                planes.append(eid_at(at_slot(pos_cube), earr))
        for eh in ehops:
            if eh[0] == "pos":
                planes.append(eid_at(at_slot(eh[1]), eh[2]))
            elif eh[0] == "run":
                planes.append(eid_at(at_slot(eh[1]) + within, eh[2]))
            else:  # prod: decompose within over (factor1, factor2) runs
                (_, s1, e1), (_, s2, e2), c2 = eh[1], eh[2], eh[3]
                c2s = jnp.maximum(at_slot(c2), 1)
                off1 = within // c2s
                off2 = within - off1 * c2s
                planes.append(eid_at(at_slot(s1) + off1, e1))
                planes.append(eid_at(at_slot(s2) + off2, e2))
        assert len(planes) == H, (len(planes), H)
        return total, keys, planes

    # ---- sweep fusion: merge combos' top-k by global coordinates ------
    n_sweep = int(np.prod(sweeps))
    strides: List[int] = []
    acc = 1
    for sc in reversed(sweeps):
        strides.append(acc)
        acc *= sc
    strides = tuple(reversed(strides))
    nk = n_axes + 1

    def kernel(dg: DeviceGraph, s, d, st_, fr, frt):
        if n_sweep == 1:
            offs = tuple(jnp.int32(0) for _ in dims)
            total, _, planes = body(dg, s, d, st_, fr, frt, offs)
            return total, jnp.stack(planes, axis=-1)

        def step(i, carry):
            tot, kacc, pacc = carry
            offs = tuple(
                ((i // strides[j]) % sweeps[j]) * jnp.int32(dims[j])
                for j in range(len(dims))
            )
            t2, keys, planes = body(dg, s, d, st_, fr, frt, offs)
            kc = jnp.concatenate([kacc, jnp.stack(keys, axis=-1)], axis=1)
            pc = jnp.concatenate([pacc, jnp.stack(planes, axis=-1)], axis=1)
            operands = tuple(kc[:, :, j] for j in range(nk)) + tuple(
                pc[:, :, h] for h in range(H)
            )
            merged = jax.lax.sort(operands, dimension=1, num_keys=nk)
            kn = jnp.stack(merged[:nk], axis=-1)[:, :kp]
            pn = jnp.stack(merged[nk:], axis=-1)[:, :kp]
            return tot + t2, kn, pn

        B = s.shape[0]
        init = (
            jnp.zeros(B, jnp.int32),
            jnp.full((B, kp, nk), _I32_MAX, jnp.int32),
            jnp.full((B, kp, H), -1, jnp.int32),
        )
        tot, _, packed = jax.lax.fori_loop(0, n_sweep, step, init)
        return tot, packed

    return kernel


def _witness_kernel(cp, strat: int, dims, sweeps, kp: int, branch: bool) -> Callable:
    """The plan's cached jitted witness kernel for one trace shape (the
    "wit" tag keeps the key disjoint from the counting-kernel keys in the
    shared, possibly cross-tick, kernels cache)."""
    key = (cp.n_iters, "wit", strat, dims, sweeps, kp, branch)
    fn = cp._kernels.get(key)  # lock-free warm path
    if fn is None:
        with cp._jit_lock:
            fn = cp._kernels.get(key)
            if fn is None:
                fn = jax.jit(
                    _build_witness_kernel(
                        cp.ir, cp.n_iters, strat, dims, sweeps, kp, branch
                    )
                )
                cp._kernels[key] = fn
    return fn


def _merge_items(counts, eids, item_cnt, item_eids, item_seed, item_eid) -> None:
    """Fold hub branch items into their seeds, in place.  Items are
    grouped by seed and in level-1 canonical order within a seed, so a
    seed's witnesses are its items' witnesses in item order; the first
    ``kp`` of them are kept, each with its item's level-1 edge id."""
    kp = eids.shape[1]
    np.add.at(counts, item_seed, item_cnt)
    found = np.minimum(item_cnt, kp)
    item = np.repeat(np.arange(len(found)), found)
    j = np.arange(len(item)) - np.repeat(np.cumsum(found) - found, found)
    seed = item_seed[item]
    rank = np.arange(len(item)) - np.searchsorted(seed, seed, side="left")
    keep = rank < kp
    tup = item_eids[item[keep], j[keep]]
    tup[:, 0] = item_eid[item[keep]]
    eids[seed[keep], rank[keep]] = tup


def mine_witnesses(
    cp,
    seed_eids: Optional[np.ndarray] = None,
    k: int = 1,
    *,
    dg: Optional[DeviceGraph] = None,
    device=None,
) -> Witnesses:
    """Mine per-seed counts AND top-k witness hop tuples for a compiled
    plan, device-resident end to end.

    Mirrors ``CompiledPattern.mine`` — witness bucket schedule, one
    ``device_put`` per group, async launches accumulated on device — with
    per-seed accumulators for bulk rows (counts scatter-add, packed eids
    scatter-set; one row per seed, so set is race-free), per-item ones
    for hub branch rows, and exactly ONE blocking device→host sync
    fetching all of them together; branch items are merged into their
    seeds host-side (:func:`_merge_items`).  ``k`` is pow2-ceiled for the
    trace key and trimmed host-side.
    """
    if k < 1:
        raise ValueError("witnesses=k must be >= 1")
    layout = witness_layout(cp.ir)
    H = len(layout)
    if seed_eids is None:
        seed_eids = np.arange(cp.g.n_edges, dtype=np.int32)
    seed_eids = np.asarray(seed_eids, dtype=np.int32)
    n = len(seed_eids)
    kp = executor.pow2ceil(max(1, int(k)))
    if n == 0:
        return Witnesses(
            pattern=cp.spec.name,
            hops=layout,
            k=int(k),
            counts=np.zeros(0, dtype=np.int64),
            n_found=np.zeros(0, dtype=np.int32),
            eids=np.full((0, int(k), H), -1, dtype=np.int64),
        )
    stats = cp.stats
    sched = cp.schedule_for(seed_eids, stats, witness=True)
    n_items = 0 if sched.item_seed is None else len(sched.item_seed)
    dgraph = cp.dg if dg is None else dg
    with jax.default_device(device):  # allocate accumulators in place
        acc = {
            False: [jnp.zeros(n, jnp.int32), jnp.full((n, kp, H), -1, jnp.int32)],
            True: [
                jnp.zeros(n_items, jnp.int32),
                jnp.full((n_items, kp, H), -1, jnp.int32),
            ],
        }
    local_keys: set = set()
    for grp in sched.groups:
        dev = jax.device_put(grp.staging, device)
        stats["bytes_h2d"] += sum(int(a.nbytes) for a in grp.staging)
        fn = _witness_kernel(cp, grp.strat, grp.dims, grp.sweeps, kp, grp.branch)
        out = acc[grp.branch]
        s0 = 0
        for w in grp.widths:
            sl = slice(s0, s0 + w)
            ss, dd, tt, ff, fft, seg = (a[sl] for a in dev)
            cnt, eids = fn(dgraph, ss, dd, tt, ff, fft)
            out[0] = out[0].at[seg].add(cnt, mode="drop")
            out[1] = out[1].at[seg].set(eids, mode="drop")
            local_keys.add(
                (cp.n_iters, "wit", grp.strat, grp.dims, grp.sweeps, kp, grp.branch, w)
            )
            stats["kernel_calls"] += 1
            stats["padded_elements"] += w * grp.per_row * grp.n_sweep
            s0 += w
    with cp._jit_lock:
        new_keys = local_keys - cp._trace_keys
        cp._trace_keys |= new_keys
    stats["jit_cache_entries"] += len(new_keys)
    stats["branch_items"] += n_items
    # THE host sync: counts and packed witness ids in one transfer
    fetched = jax.device_get((acc[False], acc[True]))
    stats["host_syncs"] += 1
    stats["bytes_d2h"] += sum(int(a.nbytes) for pair in fetched for a in pair)
    (cnt_h, eids_h), (icnt_h, ieids_h) = fetched
    counts = cnt_h.astype(np.int64)
    eids_h = np.array(eids_h)
    if n_items:
        _merge_items(counts, eids_h, icnt_h, ieids_h, sched.item_seed, sched.item_eid)
    return Witnesses(
        pattern=cp.spec.name,
        hops=layout,
        k=int(k),
        counts=counts,
        n_found=np.minimum(counts, int(k)).astype(np.int32),
        eids=eids_h[:, : int(k), :].astype(np.int64),
    )
