"""A chip's share of a many-expert layer: routing over every expert,
dropless dispatch to the experts held here, grouped matmuls, a shared
expert.

Where :class:`~apex_tpu.contrib.moe.MoEMLP` is the Switch layer (a queue
of fixed capacity an expert, overflow dropped, a ``[K*N, E]`` one-hot),
this is the layer of today's many-expert models (hundreds of small SwiGLU
experts, ten a token, one shared expert every token passes through), as
one rank of an expert-parallel deployment computes it:

- the router keeps its full width and is one of two kinds, named by
  ``router``. ``"softmax"``: probabilities over all ``num_experts`` in
  float32, the ``top_k`` largest, their weights divided by their sum; the
  balancing term is the Switch form over the batch; the shared expert
  has a sigmoid gate of its own. ``"sigmoid"`` (the bias-balanced router
  of the DeepSeek-V3 family): scores ``sigmoid(x W_r)`` in float32; the
  ``top_k`` are chosen on ``score + bias``, where ``bias`` is a float an
  expert that **no gradient reaches** (the caller's state, moved after
  each step by :meth:`ExpertLayer.moved_bias`), and weighted by the
  **unbiased** scores divided by their sum and multiplied by
  ``routed_scale``; the balancing term is taken a sequence and averaged;
  the shared expert is ungated. One field, since each of these is the
  other's consequence in the models that have them;
- the layer is told ``experts_held = (lo, hi)``, the range of experts
  whose weights it has. The (token, expert) pairs that fall in the range
  are sorted by expert into one buffer of ``dispatch_bound`` rows, each
  expert's group starting on a multiple of ``tile`` rows, so that every
  tile of the buffer belongs to one expert and the experts' three matmuls
  are grouped matmuls over tiles, each tile with its expert's weights:
  on the TPU, at widths of whole lanes, Pallas kernels that look the
  tile's expert up (``ops/pallas/grouped_matmul.py``: no copy of the
  weights a tile, no work in the tiles past the last live one); anywhere
  else, and under ``dispatch.backend("reference")`` as the kernels'
  oracle, an einsum over each tile's gathered weights. Which runs is
  read from the platform and the shapes. The rows of the buffer that
  hold no pair (the end of a group's last tile, the tiles past the last
  group) are not masked: they hold some token's row, finite, and a
  weight of zero, and the weight is what the combine multiplies by (in
  the down projection's kernel, on the tile it holds, or after the
  einsum), so such a row gets and gives no gradient in the kernels;
- the map between pairs and rows is kept both ways (:meth:`placed`).
  Rows to pairs, ``pair [rows]`` and ``tok = pair // top_k``: what the
  grouped matmuls' operand is gathered by (``xb = x[tok]``). Pairs to
  rows, ``pos [N, top_k]`` and ``ok [N, top_k]``: the row a pair sits
  in, its group's first row plus its rank among the pairs on its expert
  (a running count an expert over the pairs: no inverse permutation),
  and whether it sits in one at all (false for a pair on an absent
  expert or past the bound). ``pos.reshape(-1)[pair[r]] == r`` on every
  live row ``r``, and no ``pos`` with ``ok`` true names a dead row;
- the two sums from rows back to tokens go by that map and scatter
  nothing: the combine is ``y[n] = sum_j ok[n, j] * yb[pos[n, j]]`` in
  float32 (its transpose the rows' gather ``dy[tok]``), and the transpose
  of ``xb = x[tok]`` is ``dx[n] = sum_j ok[n, j] * dxb[pos[n, j]]``, added
  in float32 and rounded to ``x``'s type once (two ``custom_vjp``s, above
  the choice of kernels or einsum). A pair that is not ``ok`` adds
  nothing **by the mask**, whatever a dead row holds. On the TPU both are
  the kernel of ``ops/pallas/row_sum.py`` (``apex_moe_rowsum``), which is
  handed ``pos`` an expert a column and uses what the sort gives: the
  tokens of a block that chose one expert sit in consecutive rows of its
  group, so a block fetches a short run of the buffer an expert and adds
  its rows by a 0/1 product. Anywhere else they are one gather of ``[N,
  d]`` a slot and one pass that adds them. As JAX writes them the two are
  scatter-adds by ``tok`` (``zeros.at[tok].add(yb)``, and ``x[tok]``
  transposed), which XLA runs on the TPU as a pass over the buffer and a
  serial pass over the tokens: 6.9 and 6.5 ms a layer of 16,384 tokens,
  top-8, 65,536 rows of 2304 on the v5e, where the kernel takes 2.2 and
  1.0 and the gather-sum in XLA 8.4 and 6.6. **The rule** is
  ``row_sum.takes``, a static function of the row's width and bytes, the
  buffer's rows and the experts held, written once beside its readings
  (the five cells' shares and whole layers): rows in whole lanes and a
  round's chunks of every held expert within the kernel's VMEM; any
  number of tokens;
- there is no capacity an expert and no drop: an expert takes whatever
  share of the buffer its tokens need. ``dispatch_bound`` is the one
  static size. Pairs that fall past it are left out **and counted**
  (``aux["overflow_pairs"]``; 0 in a sound run), never silently lost; the
  default bound (0) is the worst case and cannot overflow;
- what the experts held elsewhere would add is left out: on one chip the
  layer runs without its exchange, and nothing stands in for other chips.
  The parts all shares give, with the shared expert counted once, add up
  to the uncut layer (``tests/test_expert_layer.py``);
- a share's router learns from its load-balancing term alone: the
  gradient of a token's weights needs all ``top_k`` of its experts'
  outputs, which come back through the exchange. The held experts' part
  alone would reward the router only for choosing the experts that are
  here, and it runs away to them, so a share holds the weights constant
  in the backward (``lax.stop_gradient``); the layer that holds every
  expert differentiates them as written.

``aux`` also carries the router's load-balancing term (softmax: Switch
form over all experts, ``E * sum_e f_e P_e`` with ``f_e`` the share of
tokens that chose expert ``e`` and ``P_e`` its mean probability; sigmoid:
``sum_e f_e P_e`` a sequence of ``T`` tokens, ``f_e = E / (K T)`` times
the sequence's pairs on ``e`` and ``P_e`` the sequence's mean of ``s_e /
sum_j s_j``, averaged over the sequences), the pairs that fell on held
experts (``held_pairs``: what the bound is sized from), the tiles of the
buffer that hold a row (``live_tiles``: what the experts' matmuls
compute; the rest of the bound is room), the load of the
fullest held expert over the mean (``load_max_over_mean``) and, for the
sigmoid router, every expert's pairs (``expert_pairs [E]``: what moves
the bias).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import ClassVar

import jax
import jax.numpy as jnp
from jax import lax

from apex_tpu.ops import dispatch
from apex_tpu.ops.pallas import grouped_matmul as gmm, row_sum

__all__ = ["ExpertLayer"]

_F32 = jnp.float32
ROUTERS = ("softmax", "sigmoid")


def _swiglu(x, p, matmuls, down=None):
    """``(silu(x W_gate) * (x W_up)) W_down`` with ``matmuls(x, *ws)``
    the float32 products of ``x`` with each of ``ws`` (``down``: the
    last product's own, where it is another)."""
    g, u = matmuls(x, p["w_gate"], p["w_up"])
    y, = (down or matmuls)((jax.nn.silu(g) * u).astype(x.dtype), p["w_down"])
    return y


def _sum_rows(buf, where, held, dtype):
    """``sum_j ok[n, j] * buf[pos[n, j]]`` for ``where = (pos, ok, local)``
    (:meth:`ExpertLayer.placed`'s map and the pairs' experts of the
    ``held``), added in float32 and rounded to ``dtype`` once: the rows of
    the buffer a token's pairs hold. A pair with ``ok`` false adds nothing
    whatever the row it names holds.

    On the TPU, where ``ops/pallas/row_sum.py`` takes the shapes, its
    kernel, which is handed the same rows an expert a column and fetches
    each live row once or twice; anywhere else one gather of ``[n, d]`` a
    slot ``j`` and one pass that adds them (XLA's gather fuses into no sum,
    and one gather of ``[n, top_k, d]`` would pad ``top_k`` to whole
    sublanes)."""
    pos, ok, local = where
    if dispatch.use_pallas() and row_sum.takes(
            buf.shape[1], buf.shape[0], held, buf.dtype.itemsize):
        return row_sum.sum_rows(buf, row_sum.columns(
            jnp.where(ok, pos, -1), local, held), dtype)

    def slot(a, j):
        return lax.index_in_dim(a, j, axis=1, keepdims=False)
    return functools.reduce(jnp.add, (
        jnp.where(slot(ok, j)[:, None], buf[slot(pos, j)].astype(_F32), 0.0)
        for j in range(pos.shape[1]))).astype(dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _rows_of(x, tok, where, held):
    """``x[tok]``, the buffer's rows; its transpose is the sum of the
    rows' cotangents by ``where`` (:func:`_sum_rows`), not a scatter-add
    by ``tok``: added in float32 and rounded to ``x``'s type once."""
    return x[tok]


def _rows_of_fwd(x, tok, where, held):
    return x[tok], where


def _rows_of_bwd(held, where, dxb):
    return _sum_rows(dxb, where, held, dxb.dtype), None, None


_rows_of.defvjp(_rows_of_fwd, _rows_of_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _combined(yb, tok, where, held):
    """``zeros.at[tok].add(yb)`` over the live rows as the sum by
    ``where`` (float32, as ``yb`` is); its transpose is the rows' gather
    ``dy[tok]``."""
    return _sum_rows(yb, where, held, _F32)


def _combined_fwd(yb, tok, where, held):
    return _combined(yb, tok, where, held), tok


def _combined_bwd(held, tok, dy):
    return dy[tok], None, None


_combined.defvjp(_combined_fwd, _combined_bwd)


@dataclasses.dataclass(frozen=True)
class ExpertLayer:
    hidden: int
    ffn: int                    # a routed expert's width
    num_experts: int            # the router's width: all experts, anywhere
    top_k: int
    experts_held: tuple = ()    # (lo, hi) of the experts here; () = all
    shared_ffn: int = 0         # the shared expert's width; 0 = none
    dispatch_bound: int = 0     # rows of the dispatch buffer; 0 = worst case
    router: str = "softmax"     # the router's kind: the module's text
    routed_scale: float = 1.0   # the sigmoid router's factor on the weights

    tile: ClassVar[int] = 128   # rows a tile: one expert's weights each

    def __post_init__(self):
        if self.router not in ROUTERS:
            raise ValueError(f"router must be one of {ROUTERS}, got "
                             f"{self.router!r}")
        lo, hi = self.held
        if not 0 <= lo < hi <= self.num_experts:
            raise ValueError(f"experts_held {self.experts_held} is no range "
                             f"of the {self.num_experts} experts")
        if not 1 <= self.top_k <= self.num_experts:
            raise ValueError(f"top_k must be in [1, {self.num_experts}]")
        if self.dispatch_bound % self.tile:
            raise ValueError(f"dispatch_bound ({self.dispatch_bound}) must "
                             f"be a multiple of tile ({self.tile})")

    @property
    def held(self) -> tuple:
        return tuple(self.experts_held) or (0, self.num_experts)

    def init(self, key, scale: float = 0.02) -> dict:
        ks = jax.random.split(key, 8)
        d, f, n = self.hidden, self.ffn, self.held[1] - self.held[0]

        def w(k, *shape):
            return jax.random.normal(k, shape) * scale
        p = {"router": w(ks[0], d, self.num_experts),
             "w_gate": w(ks[1], n, d, f), "w_up": w(ks[2], n, d, f),
             "w_down": w(ks[3], n, f, d)}
        if self.shared_ffn:
            s = self.shared_ffn
            p["shared"] = {"w_gate": w(ks[4], d, s), "w_up": w(ks[5], d, s),
                           "w_down": w(ks[6], s, d)}
            if self.router == "softmax":
                p["shared"]["gate"] = w(ks[7], d, 1)
        return p

    def bound(self, n_tokens: int) -> int:
        """Rows of the dispatch buffer: ``dispatch_bound``, or every pair
        on a held expert with each group's last tile all but empty."""
        if self.dispatch_bound:
            return self.dispatch_bound
        lo, hi = self.held
        pairs = n_tokens * min(self.top_k, hi - lo)
        return -(-(pairs + (hi - lo) * (self.tile - 1)) // self.tile) \
            * self.tile

    def route(self, params: dict, x, bias=None):
        """``(weights [N, K] float32, experts [N, K], scores [N, E])``:
        the softmax router's probabilities, or the sigmoid router's
        scores with the experts chosen on ``scores + bias [E]``."""
        logits = jnp.dot(x, params["router"], preferred_element_type=_F32)
        if self.router == "sigmoid":
            scores = jax.nn.sigmoid(logits)
            _, idx = lax.top_k(scores if bias is None
                               else scores + lax.stop_gradient(bias),
                               self.top_k)
            w = jnp.take_along_axis(scores, idx, axis=-1)
            return w / jnp.sum(w, axis=-1, keepdims=True) \
                * self.routed_scale, idx, scores
        probs = jax.nn.softmax(logits, axis=-1)
        w, idx = lax.top_k(probs, self.top_k)
        return w / jnp.sum(w, axis=-1, keepdims=True), idx, probs

    @staticmethod
    def moved_bias(bias, expert_pairs, rate: float):
        """The sigmoid router's selection bias after a step that sent
        ``expert_pairs [..., E]`` pairs to each expert: ``rate`` up for an
        expert below the mean load, ``rate`` down for one above it."""
        load = expert_pairs.astype(_F32)
        return bias + rate * jnp.sign(
            jnp.mean(load, axis=-1, keepdims=True) - load)

    def _balance(self, idx, scores, seqs: int):
        """``(pairs an expert [E], the load-balancing term)``, the pairs
        by a fused compare-and-sum: no [pairs, E] one-hot in memory."""
        n, e_all = scores.shape[0], self.num_experts
        if self.router == "softmax":
            counts = jnp.sum(idx[:, :, None] == jnp.arange(e_all), (0, 1))
            return counts, e_all * jnp.sum(
                lax.stop_gradient(counts.astype(_F32) / n)
                * jnp.mean(scores, axis=0))
        t = n // seqs
        by_seq = jnp.sum(idx.reshape(seqs, t * self.top_k, 1)
                         == jnp.arange(e_all), 1)            # [S, E]
        share = scores / jnp.sum(scores, axis=-1, keepdims=True)
        f = lax.stop_gradient(by_seq.astype(_F32)
                              * (e_all / (self.top_k * t)))
        return jnp.sum(by_seq, 0), jnp.mean(jnp.sum(
            f * jnp.mean(share.reshape(seqs, t, e_all), axis=1), axis=-1))

    def placed(self, local, n_e, rows: int) -> dict:
        """Where the pairs sit in a buffer of ``rows`` rows, both ways.
        ``local [N, K]``: a pair's held expert, ``held`` for an absent one;
        ``n_e [held]``: the pairs an expert. Rows to pairs: ``tile_e
        [rows / tile]`` a tile's expert, ``pair [rows]`` the pair a row
        holds (``n * K + j``; some pair's for a dead row) and ``live
        [rows]``, with the groups' ``tiles_e`` / ``end_tile [held]``. Pairs
        to rows: ``pos [N, K]`` the row a pair sits in and
        ``ok [N, K]``, false for a pair on an absent expert or past the
        bound (its ``pos`` is 0). ``pos.reshape(-1)[pair[r]] == r`` on
        every live row ``r``, and ``ok`` holds on exactly those pairs."""
        n, k = local.shape
        tm, held = self.tile, n_e.shape[0]
        # sort the pairs by held expert; pairs on absent experts last
        order = jnp.argsort(local.reshape(-1), stable=True)
        first = jnp.cumsum(n_e) - n_e       # in the sorted pairs
        tiles_e = -(-n_e // tm)             # whole tiles an expert
        end_tile = jnp.cumsum(tiles_e)
        # a tile belongs to one expert, so what a row needs of its
        # expert is looked up a tile (rows // tm of them) and spread
        tile = jnp.arange(rows // tm)
        tile_e = jnp.minimum(
            jnp.searchsorted(end_tile, tile, side="right",
                             method="compare_all"), held - 1)
        off = ((tile - (end_tile - tiles_e)[tile_e]) * tm)[:, None] \
            + jnp.arange(tm)                # [tiles, tm]: row in group
        live = (off < n_e[tile_e][:, None]).reshape(rows)
        pair = order[jnp.clip(first[tile_e][:, None] + off, 0,
                              n * k - 1).reshape(rows)]
        # the same map from the pairs' side: a pair's row is its group's
        # first row and its rank among the group's pairs, which a count
        # of the earlier pairs an expert gives (the tokens before it by a
        # running sum, then the token's own): no inverse permutation
        hot = local[:, :, None] == jnp.arange(held)     # [n, k, held]
        mine = jnp.sum(hot, axis=1, dtype=jnp.int32)
        rank = (jnp.cumsum(mine, axis=0) - mine)[:, None] \
            + jnp.cumsum(hot, axis=1, dtype=jnp.int32) - hot
        pos = jnp.sum(jnp.where(
            hot, (end_tile - tiles_e) * tm + rank, 0), axis=2)
        ok = (local < held) & (pos < rows)
        return {"tiles_e": tiles_e, "end_tile": end_tile, "tile_e": tile_e,
                "pair": pair, "live": live,
                "pos": jnp.where(ok, pos, 0), "ok": ok}

    def routed(self, params: dict, x, bias=None):
        """The held experts' part of the layer for ``x [N, hidden]`` or,
        in sequences, ``x [S, T, hidden]`` (the sigmoid router balances a
        sequence): ``(y float32 in x's shape, aux)``. ``bias [E]``: the
        sigmoid router's selection bias."""
        shape, d = x.shape, x.shape[-1]
        x = x.reshape(-1, d)
        n = x.shape[0]
        k, tm, e_all = self.top_k, self.tile, self.num_experts
        lo, hi = self.held
        held = hi - lo
        rows = self.bound(n)
        with jax.named_scope("moe_route"):              # prof.SCOPES
            w, idx, scores = self.route(params, x, bias)
            if held < e_all:    # a share: no reward from held experts only
                w = lax.stop_gradient(w)
            counts, balance = self._balance(idx, scores, n // shape[-2])
            local = jnp.where((idx >= lo) & (idx < hi), idx - lo, held)
            n_e = counts[lo:hi]
            put = self.placed(local, n_e, rows)
            tiles_e, end_tile, tile_e = (
                put["tiles_e"], put["end_tile"], put["tile_e"])
            pair, live = put["pair"], put["live"]
            where = (put["pos"], put["ok"], local)
            tok = pair // k
            # a dead row (a group's last tile past its pairs, the tiles
            # past the last group) holds some token's row under no mask:
            # its weight is zero, which keeps it out of the kernels'
            # gradients, and no pair's ``pos`` names it
            xb = _rows_of(x, tok, where, held)
            wb = jnp.where(live, w.reshape(-1)[pair], 0.0)
            overflow = jnp.sum(jnp.clip(
                (end_tile - tiles_e) * tm + n_e - rows, 0, n_e))
            load = jnp.max(n_e) / jnp.maximum(jnp.mean(n_e.astype(_F32)),
                                              1e-9)
            live_tiles = jnp.minimum(end_tile[-1], rows // tm).astype(
                jnp.int32)
        with jax.named_scope("moe_experts"):
            if dispatch.use_pallas() and gmm.takes(d, self.ffn, tm):
                def matmuls(x, *ws, scale=None):
                    return gmm.grouped_matmul(x, ws, tile_e, live_tiles,
                                              scale)
                # the pairs' weights ride the down projection: its kernel
                # multiplies the float32 tile it holds and writes it once
                yb = _swiglu(xb, params, matmuls,
                             functools.partial(matmuls, scale=wb))
            else:       # the kernels' oracle: each tile's weights gathered
                yb = _swiglu(
                    xb.reshape(rows // tm, tm, d), params,
                    lambda x, *ws: [jnp.einsum(
                        "tmk,tkn->tmn", x, w[tile_e],
                        preferred_element_type=_F32) for w in ws]
                ).reshape(rows, d) * wb[:, None]
        with jax.named_scope("moe_route"):
            y = _combined(yb, tok, where, held)
        aux = {"load_balance_loss": balance,
               "overflow_pairs": overflow.astype(jnp.int32),
               "held_pairs": jnp.sum(n_e).astype(jnp.int32),
               "live_tiles": live_tiles,
               "load_max_over_mean": load}
        if self.router == "sigmoid":
            aux["expert_pairs"] = counts.astype(jnp.int32)
        return y.reshape(shape), aux

    def shared(self, params: dict, x):
        """The shared expert, which every chip computes alike (gated
        under the softmax router, ungated under the sigmoid one)."""
        sp = params["shared"]
        with jax.named_scope("moe_experts"):
            gate = 1.0 if self.router == "sigmoid" else jax.nn.sigmoid(
                jnp.dot(x, sp["gate"], preferred_element_type=_F32))
            return gate * _swiglu(x, sp, lambda x, *ws: [jnp.einsum(
                "...k,kn->...n", x, w, preferred_element_type=_F32)
                for w in ws])

    def apply(self, params: dict, x, bias=None):
        """``x [N, hidden]`` or ``[S, T, hidden]`` -> ``(y in x's shape
        and type, aux)``."""
        y, aux = self.routed(params, x, bias)
        if self.shared_ffn:
            y = y + self.shared(params, x)
        return y.astype(x.dtype), aux
