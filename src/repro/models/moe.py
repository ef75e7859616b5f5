"""Mixture-of-Experts FFN: GShard-style top-k dispatch with capacity.

Formulation: tokens are grouped (B, nG, S); the router's top-k choices are
turned into a (B, nG, S, E, C) combine tensor; expert inputs/outputs move
through einsums so GSPMD shards experts on the `model` mesh axis (the
all-to-all appears in the lowered HLO). Tokens overflowing an expert's
capacity are dropped (residual passes through), as in GShard/Switch.

Two dispatch transports (``cfg.moe_dispatch``):

- ``"einsum"`` (default): the dense one-hot einsum formulation above. GSPMD
  infers the all-to-all; it is also the single-host oracle the explicit
  path is tested against.
- ``"alltoallv"``: explicit expert parallelism over a named mesh axis via
  :func:`repro.comm.palltoallv`. Tokens stay batch-sharded; experts are
  contiguously partitioned across ranks (E need not divide n — the ragged
  block sizes are exactly the ``sizes`` matrix of the schedule-IR
  alltoallv). Routing/combine math is identical to the einsum path, so the
  two agree to summation order.

Shared experts (DeepSeek/Moonlight style) run densely for every token.

DeepSeek-V3 routing (``cfg.dropless``; :func:`dropless_ffn`) replaces all
of the above: sigmoid scores over the router's ``cfg.router_width``
experts, the top-k of scores plus a per-expert correction bias, weights
from the unbiased chosen scores. Nothing is dropped and nothing is padded
to a capacity. The layer holds ``cfg.num_experts`` consecutive experts from
``first_expert`` (one chip's share under expert parallelism): the choices
of held experts are sorted into ragged groups for one grouped matmul; the
choices of experts held elsewhere add nothing here. The shared experts run
for every token.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .layers import _norm_init, down_proj

__all__ = ["init_moe", "moe_ffn", "dropless_ffn", "route_sigmoid", "expert_partition"]


def init_moe(key, cfg, dtype=jnp.bfloat16):
    d, f, E = cfg.d_model, cfg.d_ff, cfg.num_experts
    ks = jax.random.split(key, 5)
    p = {
        "router": _norm_init(ks[0], (d, cfg.router_width), d**-0.5, jnp.float32),
        "w_gate": _norm_init(ks[1], (E, d, f), d**-0.5, dtype),
        "w_up": _norm_init(ks[2], (E, d, f), d**-0.5, dtype),
        "w_down": _norm_init(ks[3], (E, f, d), f**-0.5, dtype),
    }
    if cfg.dropless:
        p["router_bias"] = jnp.zeros((cfg.router_width,), jnp.float32)
    if cfg.num_shared_experts:
        fs = f * cfg.num_shared_experts
        kss = jax.random.split(ks[4], 3)
        p["shared"] = {
            "w_gate": _norm_init(kss[0], (d, fs), d**-0.5, dtype),
            "w_up": _norm_init(kss[1], (d, fs), d**-0.5, dtype),
            "w_down": _norm_init(kss[2], (fs, d), fs**-0.5, dtype),
        }
    return p


def _capacity(S: int, k: int, E: int, cf: float) -> int:
    c = int(S * k * cf / E) + 1
    # the floor of 4 keeps tiny groups from thrashing drops, but it must
    # never exceed the S*k slot supply (S=2, k=1 has only 2 slots total)
    return max(min(4, S * k), min(c, S * k)) if S > 1 else max(1, k)


def _group_size(T: int, cfg) -> int:
    """Dispatch group length: ``cfg.moe_group_size`` when it divides T,
    else the largest divisor of T that fits (T=520, group 512 -> 260;
    prime T degrades to 1 rather than asserting)."""
    S = min(cfg.moe_group_size, T)
    if T % S:
        S = max(d for d in range(1, S + 1) if T % d == 0)
    return S


def _route(p, xg, cfg):
    """Router + capacity bookkeeping on grouped tokens (B, nG, S, D).

    Returns (combine, dispatch, me, ce): the (B, nG, S, E, C) combine /
    dispatch tensors and the load-balancing statistics — ``me`` the mean
    router probability and ``ce`` the fraction of tokens routed per expert
    (normalized by k so it sums to ~1 regardless of top-k width).
    """
    B, nG, S, D = xg.shape
    E, k = cfg.num_experts, cfg.experts_per_token

    logits = jnp.einsum("bgsd,de->bgse", xg.astype(jnp.float32), p["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(probs, k)            # (B,nG,S,k)
    gate_vals = gate_vals / jnp.maximum(
        jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9
    )  # renormalize over the selected experts

    C = _capacity(S, k, E, cfg.capacity_factor)
    onehot_e = jax.nn.one_hot(expert_idx, E, dtype=jnp.float32)  # (B,nG,S,k,E)
    # position-in-expert: cumulative count over the flattened (S, k) order
    flat = onehot_e.reshape(B, nG, S * k, E)
    pos_in_e = (jnp.cumsum(flat, axis=2) - flat).reshape(B, nG, S, k, E)
    pos_in_e = jnp.sum(pos_in_e * onehot_e, axis=-1)             # (B,nG,S,k)
    keep = pos_in_e < C
    gate_vals = gate_vals * keep.astype(gate_vals.dtype)
    onehot_c = jax.nn.one_hot(pos_in_e.astype(jnp.int32), C, dtype=jnp.float32)

    combine = jnp.einsum("bgske,bgsk,bgskc->bgsec", onehot_e, gate_vals, onehot_c)
    dispatch = (combine > 0).astype(xg.dtype)                    # (B,nG,S,E,C)
    combine = combine.astype(xg.dtype)

    # GShard load-balancing statistics (each a length-E batch mean)
    me = jnp.mean(probs, axis=(0, 1, 2))
    ce = jnp.mean(onehot_e.sum(axis=3), axis=(0, 1, 2)) / max(k, 1)
    return combine, dispatch, me, ce


def _shared_out(p, x):
    sp = p["shared"]
    hs = jax.nn.silu(x @ sp["w_gate"]) * (x @ sp["w_up"])
    return down_proj(hs, sp["w_down"])


def expert_partition(E: int, n: int) -> tuple[int, ...]:
    """Contiguous expert counts per rank: the first ``E % n`` ranks take one
    extra (E=6, n=4 -> (2, 2, 1, 1)). Ranks beyond E hold zero experts."""
    base, rem = divmod(E, n)
    return tuple(base + (1 if r < rem else 0) for r in range(n))


def moe_ffn(p, x, cfg, *, axis_name=None):
    """x: (B, T, D) -> (out, aux_loss).

    With ``axis_name`` set and ``cfg.moe_dispatch == "alltoallv"``, runs the
    explicit expert-parallel transport over that mesh axis (call inside
    ``shard_map`` with the batch sharded on the axis); otherwise the dense
    einsum formulation.
    """
    if axis_name is not None and getattr(cfg, "moe_dispatch", "einsum") == "alltoallv":
        return _moe_ffn_alltoallv(p, x, cfg, axis_name)
    B, T, D = x.shape
    E = cfg.num_experts
    S = _group_size(T, cfg)
    nG = T // S
    xg = x.reshape(B, nG, S, D)

    combine, dispatch, me, ce = _route(p, xg, cfg)

    expert_in = jnp.einsum("bgsec,bgsd->ebgcd", dispatch, xg)
    h = jax.nn.silu(jnp.einsum("ebgcd,edf->ebgcf", expert_in, p["w_gate"]))
    h = h * jnp.einsum("ebgcd,edf->ebgcf", expert_in, p["w_up"])
    expert_out = jnp.einsum(
        "ebgcf,efd->ebgcd", h, p["w_down"], preferred_element_type=h.dtype
    )
    y = jnp.einsum("bgsec,ebgcd->bgsd", combine, expert_out).reshape(B, T, D)

    if "shared" in p:
        y = y + _shared_out(p, x)

    aux = E * jnp.sum(me * ce) * cfg.router_aux_coef
    return y, aux


def _moe_ffn_alltoallv(p, x, cfg, axis_name):
    """Expert-parallel MoE over ``axis_name`` via the ragged alltoallv.

    Contract: ``x`` is the rank's batch shard (B_loc, T, D); expert weights
    are replicated. Experts partition contiguously across the n ranks
    (:func:`expert_partition` — ragged when n does not divide E). Per
    expert the dispatch tensor supplies R = B_loc * nG * C capacity rows,
    so the forward block matrix is m[s][d] = cnt[d] * R (uniform per
    destination) and the return matrix its transpose — exactly the ragged
    ``sizes`` the schedule-IR alltoallv consumes. The returned aux loss is
    the global-batch value (me/ce are pmean'd before combining), matching
    the einsum oracle run on the unsharded batch.
    """
    from ..comm.api import palltoallv

    B, T, D = x.shape
    E = cfg.num_experts
    n = lax.axis_size(axis_name)
    S = _group_size(T, cfg)
    nG = T // S
    xg = x.reshape(B, nG, S, D)

    combine, dispatch, me, ce = _route(p, xg, cfg)
    C = combine.shape[-1]
    R = B * nG * C                         # capacity rows per expert
    cnt = expert_partition(E, n)
    cnt_max = max(cnt)

    # ---- forward transport: (E, B, nG, C, D) flattened expert-major is
    # already the destination-major compact layout (experts contiguous per
    # rank). Out as padded (n, cnt_max*R, D) blocks: source s's tokens for
    # my cnt[r] local experts live in out[s]'s valid prefix.
    expert_in = jnp.einsum("bgsec,bgsd->ebgcd", dispatch, xg)
    fwd = palltoallv(
        expert_in.reshape(E * R, D), axis_name,
        sizes=[c * R for c in cnt], out_padded=True,
    )
    din = fwd.reshape(n, cnt_max, B, nG, C, D)

    # ---- local experts, padded to cnt_max with zero-masked weights: slot
    # j >= cnt[rank] computes silu(0)*0 = 0, so garbage slots are inert
    widx = np.zeros((n, cnt_max), np.int32)
    wvalid = np.zeros((n, cnt_max), bool)
    e0 = 0
    for r in range(n):
        widx[r, : cnt[r]] = np.arange(e0, e0 + cnt[r])
        wvalid[r, : cnt[r]] = True
        e0 += cnt[r]
    rank = lax.axis_index(axis_name)
    idx = jnp.asarray(widx)[rank]
    mask = jnp.asarray(wvalid)[rank][:, None, None]
    w_gate = p["w_gate"][idx] * mask
    w_up = p["w_up"][idx] * mask
    w_down = p["w_down"][idx] * mask

    h = jax.nn.silu(jnp.einsum("sjbgcd,jdf->sjbgcf", din, w_gate))
    h = h * jnp.einsum("sjbgcd,jdf->sjbgcf", din, w_up)
    eo = jnp.einsum(
        "sjbgcf,jfd->sjbgcd", h, w_down, preferred_element_type=h.dtype
    )

    # ---- return transport: block to source d is eo[d]'s valid prefix
    # (cnt[rank] local experts) — the transposed matrix, padded input
    back = palltoallv(
        eo.reshape(n, cnt_max * R, D), axis_name,
        sizes=[[c * R] * n for c in cnt], in_padded=True,
    )
    expert_out = back.reshape(E, B, nG, C, D)   # global expert order

    y = jnp.einsum("bgsec,ebgcd->bgsd", combine, expert_out).reshape(B, T, D)
    if "shared" in p:
        y = y + _shared_out(p, x)

    me_g = lax.pmean(me, axis_name)
    ce_g = lax.pmean(ce, axis_name)
    aux = E * jnp.sum(me_g * ce_g) * cfg.router_aux_coef
    return y, aux


def route_sigmoid(p, x, cfg):
    """DeepSeek-V3's router on x (B, T, D): float32 logits over the
    router's experts, ``scores = sigmoid(logits)``, the choice the top-k of
    ``scores + bias`` (the bias steers the choice and takes no gradient),
    the weights the chosen scores, normalised and scaled. Returns
    (expert ids (B, T, k), weights (B, T, k) f32, per-expert load (E,) f32,
    the sequence-wise balance loss)."""
    E, k = cfg.router_width, cfg.experts_per_token
    logits = jnp.einsum("btd,de->bte", x.astype(jnp.float32), p["router"])
    scores = jax.nn.sigmoid(logits)
    _, idx = jax.lax.top_k(scores + jax.lax.stop_gradient(p["router_bias"]), k)
    w = jnp.take_along_axis(scores, idx, axis=-1)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20) * cfg.routed_scaling_factor
    chosen = jnp.sum(jax.nn.one_hot(idx, E, dtype=jnp.float32), axis=2)   # (B, T, E)
    load = jnp.sum(chosen, axis=(0, 1))
    aux = jnp.zeros((), jnp.float32)
    if cfg.router_aux_coef:
        # DeepSeek-V3 eq. 17-20, per sequence: f_i = E/(k T) * tokens
        # choosing i; P_i = mean over t of s_i,t / sum_j s_j,t
        T = x.shape[1]
        f = jnp.sum(chosen, axis=1) * (E / (k * T))
        P = jnp.mean(scores / jnp.sum(scores, axis=-1, keepdims=True), axis=1)
        aux = cfg.router_aux_coef * jnp.mean(jnp.sum(f * P, axis=-1))
    return idx, w, load, aux


def dropless_ffn(p, x, cfg, *, first_expert: int = 0):
    """x: (B, T, D) -> (out, balance loss, per-expert load over the router's
    experts). The layer's part of the routed experts' result (the experts
    ``first_expert`` .. + ``cfg.num_experts``) plus the shared experts."""
    from ..kernels.ops import grouped_matmul

    B, T, D = x.shape
    E_held, k = cfg.num_experts, cfg.experts_per_token
    with jax.named_scope("moe"):
        with jax.named_scope("route"):
            idx, w, load, aux = route_sigmoid(p, x, cfg)
        with jax.named_scope("dispatch"):
            local = idx.reshape(-1) - first_expert
            held = (local >= 0) & (local < E_held)
            group = jnp.where(held, local, E_held)       # held groups first, the rest last
            order = jnp.argsort(group, stable=True)
            sizes = jnp.sum(jax.nn.one_hot(group, E_held + 1, dtype=jnp.int32), axis=0)[:E_held]
            rows = jnp.take(x.reshape(B * T, D), order // k, axis=0)
        with jax.named_scope("experts"):
            h = jax.nn.silu(grouped_matmul(rows, p["w_gate"], sizes)) * grouped_matmul(rows, p["w_up"], sizes)
            out = grouped_matmul(h, p["w_down"], sizes)  # rows past the held groups are zero
        with jax.named_scope("combine"):
            back = jnp.take(out, jnp.argsort(order), axis=0).reshape(B * T, k, D)
            wk = (w.reshape(B * T, k) * held.reshape(B * T, k)).astype(x.dtype)
            y = jnp.einsum("tkd,tk->td", back, wk, preferred_element_type=jnp.float32)
            y = y.astype(x.dtype).reshape(B, T, D)
        if "shared" in p:
            with jax.named_scope("shared"):
                y = y + _shared_out(p, x)
    return y, aux, load
