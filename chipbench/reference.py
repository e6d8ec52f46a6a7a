"""Plain reference of Distributed NE rounds, in numpy.

The same semantics as the program's SPMD round (paper Alg. 1-4 with §5's
multi-expansion), written out directly and importing nothing of the
program: per partition, selection of the ``ceil(lam |B_p|)`` boundary
vertices of least remaining degree (ties by vertex id, at most ``k_sel``,
cut to the prefix that fits the partition's remaining alpha-capacity,
a random restart from the seed when the boundary is empty); vertex claims
by least ``(|E_p|, p)``; one-hop allocation of each unallocated edge to
the better claim of its endpoints; then two-hop allocation of each
unallocated edge whose endpoints already share a partition under the
capacity (Condition 5), to the least loaded such partition, with each
partition's quota handed out in device order, then edge order.  The
random restarts draw from ``jax.random`` exactly as the configuration's
seed defines them; nothing else uses JAX.

``run`` steps rounds from the initial state; ``two_hop=False`` is the
control: the reference with the configuration's two-hop allocation left
out.  It models ``PartitionDriver(mode="spmd")`` alone and refuses any
other mode, so that a configuration cannot state one path while the
benchmark holds another to it.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from graphs import grid_device

I32_INF = np.iinfo(np.int32).max


@dataclasses.dataclass
class State:
    edge_part: np.ndarray       # (M,) int32, edge order; -1 unallocated
    replicas: np.ndarray        # (P, N) bool: replicas[p, v] iff v in V(E_p)
    degree_rest: np.ndarray     # (N,) int64 unallocated incident edges
    edges_per_part: np.ndarray  # (P,) int64
    key: object                 # jax PRNG key of the next round
    rounds: int
    remaining: int
    una: np.ndarray             # unallocated edge ids, in quota order
    uu: np.ndarray              # their endpoints
    vv: np.ndarray

    @property
    def vparts(self) -> np.ndarray:
        """The replica map as the program holds it, (N, P) bool."""
        return self.replicas.T


def alpha_limit(alpha: float, m: int, p: int) -> int:
    return int(alpha * m / p)


def priority(count, p, num_partitions: int):
    cap = (I32_INF - num_partitions) // num_partitions - 1
    return np.minimum(count, cap) * num_partitions + p


class Reference:
    def __init__(self, edges: np.ndarray, n: int, ne: dict,
                 num_devices: int, *, mode: str,
                 two_hop: bool | None = None):
        import jax

        if mode != "spmd":
            raise ValueError(f"the reference models mode 'spmd' alone, "
                             f"not {mode!r}")

        self.jax = jax
        self.cpu = jax.devices("cpu")[0]
        self.u = edges[:, 0].astype(np.int32)
        self.v = edges[:, 1].astype(np.int32)
        self.n, self.m = n, edges.shape[0]
        self.p = ne["num_partitions"]
        if self.p > 64:
            raise ValueError("the reference holds replica sets in 64 bits")
        self.alpha, self.lam = ne["alpha"], ne["lam"]
        self.k_sel = min(ne["k_sel"], n)
        self.max_rounds = ne["max_rounds"]
        self.seed = ne["seed"]
        self.two_hop = ne["two_hop"] if two_hop is None else two_hop
        self.limit = alpha_limit(self.alpha, self.m, self.p)
        # device-major, then edge, order: the order in which the devices'
        # two-hop candidates take each partition's quota
        self.order = np.argsort(grid_device(edges, num_devices),
                                kind="stable")
        self._uniform = jax.jit(lambda k: jax.random.uniform(k, (n,)))
        masks = np.arange(1 << 16, dtype=np.uint32)
        self._mask_bits = [((masks >> b) & 1).astype(bool) for b in range(16)]

    def init(self) -> State:
        with self.jax.default_device(self.cpu):
            key = self.jax.random.PRNGKey(self.seed)
        deg = (np.bincount(self.u, minlength=self.n)
               + np.bincount(self.v, minlength=self.n))
        return State(np.full(self.m, -1, np.int32),
                     np.zeros((self.p, self.n), bool), deg,
                     np.zeros(self.p, np.int64), key, 0, self.m,
                     self.order, self.u[self.order], self.v[self.order])

    def done(self, st: State) -> bool:
        return st.remaining <= 0 or st.rounds >= self.max_rounds

    def run(self, rounds: int | None = None) -> State:
        """Step from the initial state until done, or ``rounds`` rounds."""
        st = self.init()
        while not self.done(st) and (rounds is None or st.rounds < rounds):
            self.step(st)
        return st

    # -- one round ----------------------------------------------------------
    def _claims(self, st: State, sub) -> np.ndarray:
        jr = self.jax.random
        n, p_num = self.n, self.p
        epp = st.edges_per_part
        active = epp <= self.limit
        rest = st.degree_rest > 0
        any_rest = bool(rest.any())
        vclaim = np.full(n, I32_INF, np.int32)
        for p in range(p_num):
            if not active[p]:
                continue
            bidx = np.flatnonzero(st.replicas[p] & rest)
            enc = priority(epp[p], p, p_num)
            if bidx.size == 0:
                if not any_rest:
                    continue
                with self.jax.default_device(self.cpu):
                    g = np.asarray(self._uniform(jr.fold_in(sub, p)))
                sel = np.array([np.argmax(np.where(rest, g, -1.0))])
            else:
                k_eff = int(np.clip(np.ceil(np.float32(self.lam)
                                            * np.float32(bidx.size)),
                                    1, self.k_sel))
                d = st.degree_rest[bidx]
                if bidx.size > k_eff:
                    # the k_eff least (degree, id): all below the k-th
                    # value, then ties at it by id
                    kth = np.partition(d, k_eff - 1)[k_eff - 1]
                    low = d < kth
                    tie = np.flatnonzero(d == kth)[: k_eff - low.sum()]
                    pick = np.concatenate([np.flatnonzero(low), tie])
                else:
                    pick = np.arange(bidx.size)
                pick = pick[np.lexsort((bidx[pick], d[pick]))]
                cost = np.cumsum(d[pick])
                fits = cost <= self.limit - epp[p]
                fits[0] = True
                sel = bidx[pick[fits]]
            vclaim[sel] = np.minimum(vclaim[sel], enc)
        return vclaim

    def _apply(self, st: State, pos: np.ndarray, part: np.ndarray) -> None:
        """Allocate the unallocated edges at positions ``pos`` of
        ``st.una`` to ``part``."""
        u, v = st.uu[pos], st.vv[pos]
        st.edge_part[st.una[pos]] = part
        rest = np.ones(st.una.size, bool)
        rest[pos] = False
        st.una, st.uu, st.vv = st.una[rest], st.uu[rest], st.vv[rest]
        st.replicas[part, u] = True
        st.replicas[part, v] = True
        st.degree_rest -= (np.bincount(u, minlength=self.n)
                           + np.bincount(v, minlength=self.n))
        st.edges_per_part += np.bincount(part, minlength=self.p)
        st.remaining -= pos.size

    def step(self, st: State) -> None:
        jr = self.jax.random
        with self.jax.default_device(self.cpu):
            st.key, sub = jr.split(st.key)
        vclaim = self._claims(st, sub)

        # one-hop: an unallocated edge joins its endpoints' best claim
        k = np.minimum(vclaim[st.uu], vclaim[st.vv])
        new = np.flatnonzero(k < I32_INF)
        self._apply(st, new, (k[new] % self.p).astype(np.int32))

        if self.two_hop:
            self._two_hop(st)
        st.rounds += 1

    def _best(self, inter: np.ndarray, enc: np.ndarray) -> np.ndarray:
        """Per edge, the partition of least ``enc`` among the set bits of
        its 64-bit mask, by a table over each 16-bit chunk."""
        best_p = np.full(inter.size, -1, np.int64)
        best_e = np.full(inter.size, I32_INF, np.int64)
        for c in range(0, self.p, 16):
            te = np.full(1 << 16, I32_INF, np.int64)
            tp = np.full(1 << 16, -1, np.int64)
            for b in range(min(16, self.p - c)):
                better = self._mask_bits[b] & (enc[c + b] < te)
                te[better], tp[better] = enc[c + b], c + b
            chunk = ((inter >> np.uint64(c)) & np.uint64(0xFFFF)).astype(
                np.int64)
            e = te[chunk]
            take = e < best_e
            best_e[take], best_p[take] = e[take], tp[chunk][take]
        return best_p

    def _two_hop(self, st: State) -> None:
        p_num, epp = self.p, st.edges_per_part
        enc = priority(epp, np.arange(p_num), p_num)
        enc = np.where(epp <= self.limit, enc, I32_INF)
        quota = np.maximum(self.limit + 1 - epp, 0)
        # replica sets as 64-bit masks: one gather per endpoint
        bits = np.zeros(self.n, np.uint64)
        for p in range(p_num):
            if enc[p] < I32_INF:
                bits |= st.replicas[p].astype(np.uint64) << np.uint64(p)
        inter = bits[st.uu] & bits[st.vv]
        has = np.flatnonzero(inter)
        cand = self._best(inter[has], enc)
        # each partition's quota goes to its first candidates, in order
        keep = np.zeros(has.size, bool)
        for p in range(p_num):
            keep[np.flatnonzero(cand == p)[: quota[p]]] = True
        self._apply(st, has[keep], cand[keep].astype(np.int32))


def stats(edges: np.ndarray, edge_part: np.ndarray, n: int, p: int) -> dict:
    """rf / eb / vb of an assignment, from the edges alone."""
    ep = edge_part.astype(np.int64)
    pairs = np.concatenate([edges[:, 0].astype(np.int64) * p + ep,
                            edges[:, 1].astype(np.int64) * p + ep])
    vrep = np.bincount(np.unique(pairs) % p, minlength=p).astype(np.int64)
    ecnt = np.bincount(ep, minlength=p).astype(np.int64)
    return {"rf": float(vrep.sum()) / float(n),
            "eb": float(ecnt.max()) / max(float(ecnt.mean()), 1e-9),
            "vb": float(vrep.max()) / max(float(vrep.mean()), 1e-9)}
