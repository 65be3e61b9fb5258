"""Delegated MoE routing: expert-load counters as a Trust.

The paper's fetch-and-add microbenchmark (Fig 6) becomes load-bearing
here: per-expert token counters live under trustee ownership as a typed
``TrustSchema`` with two handles —

  add(expert, delta) -> count     fetch-and-add; returns the running
                                  total AFTER this token landed, with
                                  same-round priors resolved in request
                                  order (client id, slot order)
  get(expert)        -> count     read the live total

and the router closes the loop: each wave reads the LIVE counts through
the ``get`` handle and penalises overloaded experts before taking the
top-1, so hot experts shed tokens to cold ones without any lock around
the counter array.  A host-side tally shadows every routed assignment;
``tests/test_delegated_moe.py`` pins the delegated counters bit-equal to
that tally (counter/router agreement).

Run:  PYTHONPATH=src python examples/delegated_moe.py
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from repro.core import TrusteeGroup
from repro.core.opspec import Field, OpSpec, TrustSchema
from repro.core import routing


# ---------------------------------------------------------------------------
# the counter schema: one int32 slot per expert, mod-partitioned over
# trustees (expert e lives on trustee e % T at local row e // T)
# ---------------------------------------------------------------------------
def make_counter_schema(n_trustees: int) -> TrustSchema:
    t = n_trustees

    def local_idx(rows):
        return (rows["expert"] // t).astype(jnp.int32)

    def serve_add(state, rows, m, client):
        counts = state["counts"]
        n_local = counts.shape[0]
        # fetch-and-add with in-round request-order priors: sort by slot
        # (stable), segmented exclusive prefix sum over the sorted deltas
        idx = jnp.where(m, local_idx(rows), n_local)
        delta = jnp.where(m, rows["delta"], 0).astype(jnp.int32)
        order = jnp.argsort(idx, stable=True)
        idx_s, delta_s = idx[order], delta[order]
        incl = jnp.cumsum(delta_s)
        excl = incl - delta_s
        seg_start = jnp.searchsorted(idx_s, idx_s, side="left")
        prior = jnp.zeros_like(delta).at[order].set(excl - excl[seg_start])
        base = counts[jnp.where(m, idx, 0)]
        new = jnp.where(m, base + prior + delta, 0)
        counts = counts.at[idx].add(delta, mode="drop")
        return {**state, "counts": counts}, {"count": new}

    def serve_get(state, rows, m, client):
        idx = jnp.where(m, local_idx(rows), 0)
        return state, {"count": jnp.where(m, state["counts"][idx], 0)}

    expert_f = Field("expert", (), jnp.int32)
    delta_f = Field("delta", (), jnp.int32)
    resp = (Field("count", (), jnp.int32),)
    return TrustSchema(
        "moe_counts",
        ops=[OpSpec("add", payload=(expert_f, delta_f), response=resp,
                    writes=("count",), serve=serve_add),
             OpSpec("get", payload=(expert_f,), response=resp,
                    writes=("count",), serve=serve_get)],
        state={"counts": Field("counts", (), jnp.int32)},
        route=lambda payload, t_: routing.mod_router(payload["expert"], t_))


class DelegatedExpertCounters:
    """Facade over the counter trust: experts in, counts out."""

    def __init__(self, mesh: Mesh, n_experts: int, axis=None,
                 capacity: Optional[int] = None, local_shortcut: bool = True,
                 session=None, name: str = "moe_counts"):
        axis = axis if axis is not None else tuple(mesh.axis_names)
        group = TrusteeGroup(mesh, axis)
        t = group.n_trustees
        self.n_experts = n_experts
        self.n_padded = ((n_experts + t - 1) // t) * t
        self.t = t
        schema_factory = lambda t_: make_counter_schema(t_)
        self.trust = group.entrust(
            {"counts": jnp.zeros((self.n_padded,), jnp.int32)},
            schema=schema_factory(t), capacity=capacity,
            local_shortcut=local_shortcut, session=session, name=name,
            schema_factory=schema_factory)

    def add(self, experts, deltas=None) -> np.ndarray:
        experts = jnp.asarray(experts, jnp.int32)
        if deltas is None:
            deltas = jnp.ones(experts.shape, jnp.int32)
        r = self.trust.op.add(experts, jnp.asarray(deltas, jnp.int32))
        return np.asarray(r["count"])

    def get(self, experts) -> np.ndarray:
        r = self.trust.op.get(jnp.asarray(experts, jnp.int32))
        return np.asarray(r["count"])

    def add_then(self, experts, deltas=None, then=None):
        experts = jnp.asarray(experts, jnp.int32)
        if deltas is None:
            deltas = jnp.ones(experts.shape, jnp.int32)
        return self.trust.op.add.then(experts,
                                      jnp.asarray(deltas, jnp.int32),
                                      then=then)

    def dump(self) -> np.ndarray:
        """Counts in expert order (host gather; tests/reporting only)."""
        owner_major = np.asarray(self.trust.trustee_state()["counts"])
        n_local = self.n_padded // self.t
        out = np.zeros_like(owner_major)
        for i in range(self.t):
            out[np.arange(i, self.n_padded, self.t)] = \
                owner_major[i * n_local:(i + 1) * n_local]
        return out[: self.n_experts]


# ---------------------------------------------------------------------------
# the toy router: live counts bias the top-1 choice toward cold experts
# ---------------------------------------------------------------------------
def route_wave(logits: np.ndarray, counts: np.ndarray, lam: float,
               tokens_per_wave: int) -> np.ndarray:
    """Top-1 over load-penalised logits.  The penalty is the expert's
    surplus over a perfectly balanced share, in units of one wave."""
    if lam > 0.0:
        surplus = (counts - counts.mean()) / max(1, tokens_per_wave)
        logits = logits - lam * surplus[None, :]
    return np.argmax(logits, axis=-1).astype(np.int32)


def run_routing(mesh: Mesh, n_experts: int = 16, n_tokens: int = 64,
                n_waves: int = 30, lam: float = 1.0, seed: int = 0,
                verbose: bool = False):
    """Drive ``n_waves`` routing waves through the delegated counters.

    Returns a dict with the delegated counts, the host-side tally of every
    routed assignment (the agreement target), the unbiased baseline's
    tally, and both load-imbalance numbers (max load / mean load)."""
    rng = np.random.default_rng(seed)
    counters = DelegatedExpertCounters(mesh, n_experts,
                                       capacity=max(n_tokens, n_experts))
    # intrinsic popularity skew: without feedback, hot experts stay hot
    popularity = np.zeros((n_experts,), np.float32)
    popularity[: max(1, n_experts // 8)] = 1.5
    host_tally = np.zeros((n_experts,), np.int64)
    base_tally = np.zeros((n_experts,), np.int64)
    assignments = []
    for w in range(n_waves):
        logits = rng.normal(size=(n_tokens, n_experts)).astype(np.float32)
        logits += popularity[None, :]
        live = counters.get(np.arange(n_experts, dtype=np.int32))
        assign = route_wave(logits, live.astype(np.float64), lam, n_tokens)
        base_tally += np.bincount(np.argmax(logits, -1), minlength=n_experts)
        running = counters.add(assign)
        host_tally += np.bincount(assign, minlength=n_experts)
        assignments.append(assign)
        # the add handle's running totals must agree with the host replay
        # of this wave in request order (single client: slot order)
        replay = live.astype(np.int64).copy()
        for i, e in enumerate(assign):
            replay[e] += 1
            assert running[i] == replay[e], (w, i)
        if verbose:
            print(f"wave {w:3d}  max-load {host_tally.max():5d}  "
                  f"biased-imbalance "
                  f"{host_tally.max() / max(1.0, host_tally.mean()):.3f}")
    mean = max(1.0, float(host_tally.mean()))
    return {
        "counters": counters,
        "delegated": counters.dump().astype(np.int64),
        "host_tally": host_tally,
        "assignments": np.concatenate(assignments),
        "imbalance_biased": float(host_tally.max()) / mean,
        "imbalance_unbiased": float(base_tally.max()) /
            max(1.0, float(base_tally.mean())),
    }


def main():
    mesh = Mesh(np.array(jax.devices()).reshape(1, -1), ("data", "model"))
    res = run_routing(mesh, verbose=True)
    agree = bool(np.array_equal(res["delegated"], res["host_tally"]))
    print("\ndelegated counts == host tally of routed tokens:", agree)
    print(f"imbalance (max/mean)  unbiased {res['imbalance_unbiased']:.3f}"
          f"  ->  load-aware {res['imbalance_biased']:.3f}")
    if not agree:
        raise SystemExit(1)


if __name__ == "__main__":
    from repro.runtime import enable_compile_cache
    enable_compile_cache()
    main()
