"""Expert-parallel MoE dispatch via shard_map + all_to_all.

§Perf hillclimb #1 (deepseek-v3-671b x train_4k).  The pjit baseline
(repro/models/moe.py) materializes a (T*k, d) repeated-token tensor and
scatter-adds it into an expert-sharded (E, C, d) buffer; XLA resolves the
token-shard -> expert-shard mismatch by all-gathering/all-reducing the
240 GB repeated tensor per layer (~42 TB/device/step observed in the
baseline HLO — the dominant roofline term).

Here the dispatch is explicit: tokens are sharded over the EP axis
group, each device builds a per-destination send buffer sized by a local
capacity, one ``lax.all_to_all`` moves tokens to their experts, the
expert FFN runs fully locally (one or a few experts per device, weights
resident), and a reverse all_to_all returns outputs.  Per-device traffic
drops to ~2 x T_loc*k*cf*d bytes per layer — the information-theoretic
all-to-all volume of expert parallelism.

Differentiable end-to-end (all_to_all/scatter/gather have transposes);
used inside the scanned layer body under jax.checkpoint.
"""
from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import MoEConfig
from repro.models.common import activation_fn
from repro.models.ffn import apply_ffn
from repro.models.moe import load_balance_loss, router_topk


def _round8(x: int) -> int:
    return max(8, -(-x // 8) * 8)


def apply_moe_ep(p, x, moe: MoEConfig, *, mesh, ep_axes: Tuple[str, ...],
                 token_axes: Tuple[str, ...], activation: str,
                 capacity_mult: float = 2.0):
    """Expert-parallel MoE FFN.  x: (B, S, d) -> (out, aux_loss).

    ep_axes:    mesh axes the EXPERT dim is sharded over (must divide E;
                the all_to_all runs over this axis group).
    token_axes: mesh axes the flat token dim is sharded over inside the
                shard_map (superset of ep_axes, e.g. +"pod").
    Expert weights must be sharded P(ep_axes, None, None) — enforced by
    sharding_rules() when EP is enabled.
    """
    B, S, d = x.shape
    T = B * S
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    n_ep = math.prod(sizes[a] for a in ep_axes)
    n_tok = math.prod(sizes[a] for a in token_axes)
    E, k = moe.num_experts, moe.experts_per_token
    assert E % n_ep == 0, (E, n_ep)
    E_loc = E // n_ep
    assert T % n_tok == 0
    T_loc = T // n_tok
    # per-(src, dst) send capacity
    cap_s = _round8(int(T_loc * k * capacity_mult / n_ep))
    n_recv = n_ep * cap_s
    cap_e = _round8(int(n_recv * capacity_mult / E_loc)) if E_loc > 1 else 0

    xt = x.reshape(T, d)
    # router runs in the pjit world (small tensors)
    logits = xt.astype(jnp.float32) @ p["router"]
    weights, ids, probs = router_topk(logits, k)
    aux = load_balance_loss(probs, ids, E) * moe.router_aux_loss

    ep_spec = ep_axes if len(ep_axes) > 1 else ep_axes[0]
    tok_spec = token_axes if len(token_axes) > 1 else token_axes[0]

    def local(xt_l, ids_l, w_l, wg, wi, wo):
        # xt_l: (T_loc, d); ids_l/w_l: (T_loc, k); wg/wi: (E_loc, d, de)
        dest = ids_l // E_loc                              # target device
        eloc = ids_l % E_loc
        flat_dest = dest.reshape(-1)                       # (T_loc*k,)
        oh = jax.nn.one_hot(flat_dest, n_ep, dtype=jnp.int32)
        incl = jnp.cumsum(oh, axis=0)
        pos = jnp.take_along_axis(incl - oh, flat_dest[:, None], axis=1)[:, 0]
        keep = pos < cap_s
        posc = jnp.where(keep, pos, cap_s - 1)
        contrib = jnp.repeat(xt_l, k, axis=0) * keep[:, None].astype(xt_l.dtype)
        send = jnp.zeros((n_ep, cap_s, d), xt_l.dtype
                         ).at[flat_dest, posc].add(contrib)
        send_el = jnp.zeros((n_ep, cap_s), jnp.int32
                            ).at[flat_dest, posc].max(
            jnp.where(keep, eloc.reshape(-1) + 1, 0))

        recv = jax.lax.all_to_all(send, ep_axes, 0, 0)     # (n_ep, cap_s, d)
        recv_el = jax.lax.all_to_all(send_el[..., None], ep_axes, 0, 0)[..., 0]
        toks = recv.reshape(n_recv, d)
        el = recv_el.reshape(n_recv)                       # 0 = empty slot

        act = activation_fn(activation)
        if E_loc == 1:
            h = act(toks @ wg[0]) * (toks @ wi[0])
            out = (h @ wo[0]) * (el > 0)[:, None].astype(toks.dtype)
        else:
            # inner local dispatch to E_loc experts
            e_idx = jnp.maximum(el - 1, 0)
            oh2 = jax.nn.one_hot(e_idx, E_loc, dtype=jnp.int32) \
                * (el > 0)[:, None]
            incl2 = jnp.cumsum(oh2, axis=0)
            pos2 = jnp.take_along_axis(incl2 - oh2, e_idx[:, None],
                                       axis=1)[:, 0]
            keep2 = (pos2 < cap_e) & (el > 0)
            pos2c = jnp.where(keep2, pos2, cap_e - 1)
            buf = jnp.zeros((E_loc, cap_e, d), toks.dtype
                            ).at[e_idx, pos2c].add(
                toks * keep2[:, None].astype(toks.dtype))
            h = act(jnp.einsum("ecd,edf->ecf", buf, wg)) \
                * jnp.einsum("ecd,edf->ecf", buf, wi)
            obuf = jnp.einsum("ecf,efd->ecd", h, wo)
            out = obuf[e_idx, pos2c] * keep2[:, None].astype(toks.dtype)

        back = jax.lax.all_to_all(out.reshape(n_ep, cap_s, d), ep_axes, 0, 0)
        gathered = back[flat_dest, posc] \
            * (keep[:, None] & True).astype(xt_l.dtype) \
            * w_l.reshape(-1, 1).astype(xt_l.dtype)
        return jnp.sum(gathered.reshape(T_loc, k, d), axis=1)

    out = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(tok_spec, None), P(tok_spec, None), P(tok_spec, None),
                  P(ep_spec, None, None), P(ep_spec, None, None),
                  P(ep_spec, None, None)),
        out_specs=P(tok_spec, None),
    )(xt, ids, weights.astype(xt.dtype), p["w_gate"], p["w_in"], p["w_out"])

    if moe.num_shared_experts > 0:
        # stay in the flat token-sharded world: one boundary reshard total
        xt_c = jax.lax.with_sharding_constraint(xt, P(tok_spec, None))
        sh = apply_ffn(p["shared"], xt_c, activation=activation, glu=True)
        out = out + sh
    out = out.reshape(B, S, d)
    return out, aux
