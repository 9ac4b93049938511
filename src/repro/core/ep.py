"""Expert parallelism via shard_map + lax.all_to_all (the paper's dispatch path).

Megatron MemFine moves tokens between EP ranks with NCCL all-to-alls around
each expert's GEMM; the JAX/TPU analogue is a ``jax.shard_map`` region over
the ``model`` mesh axis with explicit ``lax.all_to_all`` collectives, one
dispatch + one combine per FCDA chunk (docs/DESIGN.md §2).

Buffer sizing is the heart of the memory story: under dropless routing the
send block per peer must hold the worst case (every local token-slot targets
one peer -> cap_send = T_chunk*K) and the local expert buffer the group worst
case (every group token lands on one local expert -> cap_recv = P*T_chunk).
Unchunked, that is the paper's `s' -> e*s` blow-up *by construction*; FCDA
divides both by the chunk count c.

The chunk body is expressed as ``ChunkStages`` (route+dispatch / expert
compute / combine) so the pipelined schedule (docs/DESIGN.md §Pipeline) can
overlap chunk i+1's dispatch all-to-all with chunk i's FFN and chunk i-1's
draining combine; ``pipeline=1`` composes the same stages back into the
sequential FCDA loop.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.configs.base import MoEConfig
from repro.core import dispatch as dsp
from repro.core.chunking import ChunkStages, chunked_pipeline
from repro.core.placement import PlacementSpec, place_expert_idx
from repro.core.router import route
from repro.kernels.ops import (combine_rows, dispatch_rows, expert_ffn,
                               moe_ffn as fused_moe_leg, ragged_expert_ffn)

#: default ragged-layout row-block size; per-run override via
#: DistContext.ragged_block (core/moe.py)
RAGGED_BLOCK = 128


def _ep_local(x_l, router_w, router_b, w1, w3, w2, *, moe_cfg: MoEConfig,
              chunks: int, remat: bool, ep_axis: str, all_axes: tuple,
              use_pallas: bool, ragged: bool = False,
              interpret: bool = False, pipeline: int = 1,
              ragged_block: int = RAGGED_BLOCK, fused: bool = False,
              placement: PlacementSpec | None = None):
    """Per-device body. x_l: (B_l, S_l, d) local tokens."""
    peers = lax.axis_size(ep_axis)
    E = moe_cfg.num_experts
    # With a placement the dispatch groups are weight SLOTS, not expert ids:
    # the single-sort planner is group-id agnostic, so sorting by slot id
    # still groups by target peer (slots are peer-contiguous by construction)
    # and the counts-matrix reconstruction on the receiver is unchanged
    # (docs/DESIGN.md §Placement).  e_local below is slots-per-peer.
    if placement is not None:
        if placement.num_experts != E or placement.num_peers != peers:
            raise ValueError(
                f"placement for (E={placement.num_experts}, "
                f"P={placement.num_peers}), layer has (E={E}, P={peers})")
        n_groups = placement.total_slots
    else:
        n_groups = E
    e_local = n_groups // peers
    b_l, s_l, d = x_l.shape
    tokens = b_l * s_l
    x2 = x_l.reshape(tokens, d)
    k = moe_cfg.top_k
    t_c = tokens // chunks                 # uniform chunk split (static)

    def stage_dispatch(xc):
        """Route + single-sort plan + dispatch all-to-all (in-flight state)."""
        r = route({"w": router_w, "bias": router_b}, xc, moe_cfg)
        # placement: expert id -> weight-slot id, replicas split by token
        # index parity (deterministic; identity spec short-circuits)
        sel = place_expert_idx(r.expert_idx, placement)
        if moe_cfg.capacity_mode == "dropless":
            # a token's k experts are distinct, so at most min(k, E_local) of
            # its slots can target one peer, and at most one can land on a
            # given expert/slot — exact worst cases, not heuristics (a peer
            # hosts each expert in at most one slot, so this survives
            # replication unchanged)
            cap_send = t_c * min(k, e_local)
        else:
            cap_send = dsp.balanced_capacity(t_c, k, peers, moe_cfg.capacity_factor)
        # ---- dispatch: ONE stable argsort per chunk plans everything ------
        # sorting by global group id (expert, or slot under placement)
        # groups by target device too (groups are contiguous per peer), and
        # within each peer block rows arrive group-sorted, so the receiver
        # places rows with cumsums over the exchanged counts matrix — no
        # second sort (docs/DESIGN.md §Dispatch)
        uplan = dsp.make_unified_plan(sel, n_groups, peers,
                                      cap_send=cap_send)
        send = dispatch_rows(xc, uplan.send_slots, peers * cap_send,
                             use_pallas=use_pallas, interpret=interpret)
        send = send.reshape(peers, cap_send, d)                    # (P, cap_s, d)
        recv = lax.all_to_all(send, ep_axis, 0, 0, tiled=True)
        recv_cnt = lax.all_to_all(uplan.counts, ep_axis, 0, 0, tiled=True)
        return {"recv": recv, "recv_cnt": recv_cnt,
                "send_slots": uplan.send_slots, "weights": r.weights,
                "aux_loss": r.aux_loss, "load": r.load,
                "drops_send": uplan.drops}

    def stage_compute(st):
        """Local expert FFN over the received rows."""
        recv, recv_cnt = st["recv"], st["recv_cnt"]
        _, cap_send, _ = recv.shape
        if moe_cfg.capacity_mode == "dropless":
            cap_recv = peers * t_c
        else:
            cap_recv = dsp.balanced_capacity(peers * t_c, k, E,
                                             moe_cfg.capacity_factor)
        # no expert-id buffer travels with the rows: each source block is
        # expert-sorted and packed from 0, so the counts matrix alone
        # reconstructs every row's expert (dsp.eids_from_counts)
        rows = recv.reshape(peers * cap_send, d)
        local_e = dsp.eids_from_counts(recv_cnt, cap_send)
        if ragged or fused:
            # MegaBlocks-style flat layout: R worst-case rows + block padding
            # instead of (E_local, cap_recv) per-expert buffers — E_local/k
            # fewer buffer rows, and the Pallas kernels predicate off blocks
            # past the actual load (docs/DESIGN.md §Perf).
            R = peers * cap_send + e_local * ragged_block
            R = -(-R // ragged_block) * ragged_block
            plan_r = dsp.recv_ragged_plan(recv_cnt, local_e, R, ragged_block)
            if fused:
                # single-launch leg (kernels/fused_moe.py): dispatch +
                # SwiGLU + down-proj + combine in one persistent kernel —
                # the (R, d) buffer never materializes in HBM on forward.
                # The router weight is applied after the return all-to-all
                # (stage_combine), so this combine is unweighted.
                back = fused_moe_leg(rows, w1, w3, w2, plan_r.slots,
                                     plan_r.block_to_expert,
                                     plan_r.total_rows, None,
                                     block_m=ragged_block,
                                     use_pallas=use_pallas,
                                     interpret=interpret)
            else:
                buf = dispatch_rows(rows, plan_r.slots, R,
                                    total_rows=plan_r.total_rows,
                                    use_pallas=use_pallas,
                                    interpret=interpret)
                h = ragged_expert_ffn(buf, w1, w3, w2,
                                      plan_r.block_to_expert,
                                      plan_r.total_rows,
                                      block_m=ragged_block,
                                      use_pallas=use_pallas,
                                      interpret=interpret)
                back = combine_rows(h, plan_r.slots, None, plan_r.total_rows,
                                    use_pallas=use_pallas,
                                    interpret=interpret)
            back = back.reshape(peers, cap_send, d)
            drops_e = plan_r.drops
        else:
            # (E_local, cap_recv) layout is flat (E_local*cap_recv, d) to
            # the dispatch kernels (occupancy is not a prefix here, so no
            # total_rows predication — only the -1-slot masking applies)
            plan_e = dsp.recv_expert_plan(recv_cnt, local_e, cap_recv)
            buf = dispatch_rows(rows, plan_e.slots, e_local * cap_recv,
                                use_pallas=use_pallas, interpret=interpret)
            h = expert_ffn(buf.reshape(e_local, cap_recv, d), w1, w3, w2,
                           use_pallas=use_pallas, interpret=interpret)
            back = combine_rows(h.reshape(e_local * cap_recv, d),
                                plan_e.slots, use_pallas=use_pallas,
                                interpret=interpret)
            back = back.reshape(peers, cap_send, d)
            drops_e = plan_e.drops
        return {"back": back, "send_slots": st["send_slots"],
                "weights": st["weights"], "aux_loss": st["aux_loss"],
                "load": st["load"],
                "drops": st["drops_send"] + drops_e}

    def stage_combine(st):
        """Combine all-to-all: return rows to their senders, weight, reduce."""
        back = st["back"]
        _, cap_send, _ = back.shape
        recv_back = lax.all_to_all(back, ep_axis, 0, 0, tiled=True)
        y = combine_rows(recv_back.reshape(peers * cap_send, d),
                         st["send_slots"], st["weights"],
                         use_pallas=use_pallas, interpret=interpret)
        stats = {
            "aux_loss": lax.pmean(st["aux_loss"], all_axes),
            "load": lax.psum(st["load"].astype(jnp.float32), all_axes),
            "drops": lax.psum(st["drops"].astype(jnp.float32), all_axes),
        }
        return y, stats

    stages = ChunkStages(stage_dispatch, stage_compute, stage_combine)
    # chunked_pipeline composes the stages back into the sequential loop
    # when depth or the chunk count rules the pipeline out
    y, stats = chunked_pipeline(stages, x2, chunks, depth=pipeline,
                                remat=remat)
    return y.reshape(b_l, s_l, d), stats


def moe_ffn_ep(params: dict, x: jax.Array, moe_cfg: MoEConfig, mesh, *,
               batch_axes: tuple = ("data",), ep_axis: str = "model",
               chunks: int = 1, remat: bool = True,
               use_pallas: bool = False, ragged: bool = False,
               interpret: bool = False, pipeline: int = 1,
               ragged_block: int = RAGGED_BLOCK, fused: bool = False,
               placement: PlacementSpec | None = None):
    """x: (B, S, d) global -> (y, stats).  B sharded over batch_axes, S over
    ep_axis (the EP group = one row of the model axis).  ``pipeline`` is the
    FCDA schedule depth: 1 = sequential loop, >= 2 = overlapped chunks.
    ``fused`` runs the local expert leg as ONE kernel launch over the ragged
    layout (kernels/fused_moe.py) instead of dispatch/FFN/combine.
    ``placement`` re-homes expert weights across EP peers (and replicates
    hot experts) per docs/DESIGN.md §Placement; identity/None is the
    hardcoded contiguous mapping."""
    all_axes = tuple(batch_axes) + (ep_axis,)
    if placement is not None and placement.is_identity:
        placement = None            # bitwise-identical fast path
    w1, w3, w2 = params["w1"], params["w3"], params["w2"]
    if placement is not None:
        placement.validate()
        # Re-home the expert weights into slot order.  This global gather of
        # the EP-sharded canonical weights IS the migration all-to-all on a
        # real mesh (each peer pulls the slices its slots need); under
        # autodiff its transpose scatter-adds every replica's gradient back
        # into the canonical (E, d, f) rows.
        idx = jnp.asarray(placement.slot_to_expert, dtype=jnp.int32)
        w1, w3, w2 = w1[idx], w3[idx], w2[idx]
    fn = functools.partial(
        _ep_local, moe_cfg=moe_cfg, chunks=chunks, remat=remat,
        ep_axis=ep_axis, all_axes=all_axes, use_pallas=use_pallas,
        ragged=ragged, interpret=interpret, pipeline=pipeline,
        ragged_block=ragged_block, fused=fused, placement=placement)
    x_spec = P(tuple(batch_axes), ep_axis, None)
    stats_spec = {"aux_loss": P(), "load": P(None), "drops": P()}
    return jax.shard_map(
        fn, mesh=mesh,
        in_specs=(x_spec, P(None, None), P(None),
                  P(ep_axis, None, None), P(ep_axis, None, None),
                  P(ep_axis, None, None)),
        out_specs=(x_spec, stats_spec),
        # pallas_call (interpret) emits ShapeDtypeStructs without vma info;
        # manual-axis correctness is covered by tests/test_distributed.py
        check_vma=False,
    )(x, params["router"]["w"], params["router"]["bias"], w1, w3, w2)
