"""Query routers (paper §3): next-ready, hash, landmark, embed.

All four share one interface: given a batch of query nodes and the current
per-processor load vector, produce a processor assignment per query and an
updated router state. Routing is sequential *in effect* (assignment i sees
the loads produced by assignments < i, and embed's EMA update is per-query,
Eq. 5); we implement it as a `lax.scan` over the batch -- the per-step work
is O(P·D), matching the paper's O(P)/O(PD) decision cost, so the scan is
cheap and jit-able.

Load-balanced distance (Eq. 3 / Eq. 7):

    d_LB(u, p) = d(u, p) + load(p) / load_factor

Query stealing (Requirement 2) shows up twice, as in the paper:
  - softly, through the load term (busy processors look "farther");
  - hard idle-stealing in the serving loop: an idle processor takes the next
    queued query of the most-loaded one (router-side, §3.2).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.landmarks import LandmarkIndex, UNREACHED
from repro.core.embedding import GraphEmbedding


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class RouterState:
    """Dynamic router state; static tables live in the Router object."""

    load: jax.Array  # (P,) float32 -- queue length per processor
    ema: jax.Array  # (P, D) float32 -- embed routing mean coordinates (Eq. 5)
    rr: jax.Array  # () int32 -- round-robin pointer (next_ready tie-break)


@dataclasses.dataclass(frozen=True)
class RouterConfig:
    scheme: str = "embed"  # next_ready | hash | landmark | embed
    load_factor: float = 20.0  # paper default
    alpha: float = 0.5  # EMA smoothing (Eq. 5), paper default
    steal_margin: float = 4.0  # hard-steal when load gap exceeds this


class Router:
    """Static routing tables + pure routing step functions."""

    def __init__(
        self,
        n_processors: int,
        config: RouterConfig,
        landmark_index: Optional[LandmarkIndex] = None,
        embedding: Optional[GraphEmbedding] = None,
        seed: int = 0,
    ):
        self.P = n_processors
        self.config = config
        self.scheme = config.scheme
        # the scheme's O(n) routing table, placed on the device once. Jitted
        # callers take it as an ARGUMENT (`route_batch(..., tables=)`), so it
        # never becomes a constant of a compiled program.
        if self.scheme == "landmark":
            assert landmark_index is not None, "landmark routing needs a LandmarkIndex"
            dtp = landmark_index.dist_to_proc.astype(np.float32)
            dtp = np.where(dtp >= float(UNREACHED), 1e6, dtp)
            self.tables = {"dist_to_proc": jax.device_put(dtp)}  # (n, P)
        elif self.scheme == "embed":
            assert embedding is not None, "embed routing needs a GraphEmbedding"
            self.tables = {"coords": jax.device_put(embedding.coords)}  # (n, D)
        else:
            self.tables = {}
        self.dim = int(embedding.coords.shape[1]) if embedding is not None else 1
        self._seed = seed

    # -- state ---------------------------------------------------------------

    def init_state(self) -> RouterState:
        # paper: EMA initialized uniformly at random
        key = jax.random.PRNGKey(self._seed)
        if "coords" in self.tables:
            coords = self.tables["coords"]
            lo = jnp.min(coords, 0)
            hi = jnp.max(coords, 0)
            ema = jax.random.uniform(key, (self.P, self.dim)) * (hi - lo) + lo
        else:
            ema = jnp.zeros((self.P, self.dim), jnp.float32)
        return RouterState(
            load=jnp.zeros((self.P,), jnp.float32),
            ema=ema,
            rr=jnp.zeros((), jnp.int32),
        )

    # -- per-query decision (scanned) ----------------------------------------

    def _decide_one(self, tables: dict, state: RouterState, q: jax.Array
                    ) -> Tuple[RouterState, jax.Array]:
        cfg = self.config
        load_term = state.load / cfg.load_factor
        if self.scheme == "next_ready":
            # pure load balance; round-robin among minima
            score = state.load + (jnp.arange(self.P) == state.rr % self.P) * (-1e-3)
            p = jnp.argmin(score).astype(jnp.int32)
            new_state = dataclasses.replace(
                state, load=state.load.at[p].add(1.0), rr=state.rr + 1
            )
            return new_state, p
        if self.scheme == "hash":
            x = q.astype(jnp.uint32)
            x = (x ^ (x >> 16)) * jnp.uint32(0x7FEB352D)
            x = (x ^ (x >> 15)) * jnp.uint32(0x846CA68B)
            p0 = ((x ^ (x >> 16)) % jnp.uint32(self.P)).astype(jnp.int32)
            # hard steal: if assigned processor is overloaded vs the idlest
            idle = jnp.argmin(state.load).astype(jnp.int32)
            steal = state.load[p0] - state.load[idle] > cfg.steal_margin
            p = jnp.where(steal, idle, p0)
            return dataclasses.replace(state, load=state.load.at[p].add(1.0)), p
        if self.scheme == "landmark":
            d = tables["dist_to_proc"][q]  # (P,)
            p = jnp.argmin(d + load_term).astype(jnp.int32)  # Algorithm 2
            return dataclasses.replace(state, load=state.load.at[p].add(1.0)), p
        if self.scheme == "embed":
            x = tables["coords"][q]  # (D,)
            d1 = jnp.sqrt(jnp.sum((state.ema - x[None, :]) ** 2, -1) + 1e-12)
            p = jnp.argmin(d1 + load_term).astype(jnp.int32)  # Algorithm 4
            a = cfg.alpha
            new_ema = state.ema.at[p].set(a * state.ema[p] + (1.0 - a) * x)  # Eq. 5
            return (
                dataclasses.replace(state, ema=new_ema, load=state.load.at[p].add(1.0)),
                p,
            )
        raise ValueError(f"unknown scheme {self.scheme}")

    # -- batched routing -------------------------------------------------------

    def route_batch(self, state: RouterState, queries: jax.Array,
                    tables: Optional[dict] = None) -> Tuple[RouterState, jax.Array]:
        """Assign a batch of queries sequentially (paper's router is a single
        thread dispatching one query at a time). queries: (B,) int32; negative
        entries are padding -- they get assignment -1 and leave the router
        state (load, EMA, rr) untouched, so fixed-shape round batches can be
        padded freely. Returns (state', assignment (B,) int32).

        `tables` defaults to `self.tables`; a jitted caller passes its own
        traced copy so the O(n) table stays an argument of its program."""
        return self._route(self.tables if tables is None else tables, state, queries)

    @functools.partial(jax.jit, static_argnames=("self",))
    def _route(self, tables: dict, state: RouterState, queries: jax.Array):
        def step(st, q):
            st2, p = self._decide_one(tables, st, jnp.maximum(q, 0))
            ok = q >= 0
            st3 = jax.tree.map(lambda new, old: jnp.where(ok, new, old), st2, st)
            return st3, jnp.where(ok, p, -1)

        return jax.lax.scan(step, state, queries)

    def complete(self, state: RouterState, processor: jax.Array, k: float = 1.0) -> RouterState:
        """Processor acknowledged completion of k queries (paper: router
        decrements that connection's queue)."""
        return dataclasses.replace(
            state, load=state.load.at[processor].add(-float(k))
        )

    def __hash__(self):  # jit static argname support
        return id(self)

    def __eq__(self, other):
        return self is other
