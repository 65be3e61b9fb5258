"""Quickstart: the typed Trust<T> API in five minutes (paper Figs. 1-3).

The Rust original is TYPE-safe as well as memory-safe: entrusted state is
unreachable except through statically checked operations.  The SPMD
translation of that contract is the declarative spec layer (DESIGN.md §10):
declare ``Field``s, ``OpSpec``s and a ``TrustSchema``; ``entrust`` derives
the runtime op table, the response structure and the routing rule, and the
Trust grows typed op handles — ``trust.op.inc(deltas)`` — that validate
every argument BEFORE anything rides the channel.

Run:  PYTHONPATH=src python examples/quickstart.py
"""
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from repro.core import (DelegatedKVStore, Field, OpSpec, SchemaError,
                        TrusteeGroup, TrustSchema, current_session)


def main():
    # a mesh over whatever devices exist (1 on a laptop; 256 on a pod —
    # same code); every chip is both client and trustee (paper's default)
    devs = np.array(jax.devices())
    mesh = Mesh(devs.reshape(1, len(devs)), ("data", "model"))

    # --- Fig. 1: entrust a counter, apply typed ops to it -------------------
    def inc(state, rows, m, client):
        delta = jnp.where(m, rows["delta"], 0.0)
        return ({"ct": state["ct"].at[0].add(jnp.sum(delta))},
                {"value": jnp.broadcast_to(state["ct"][0], m.shape)})

    # the schema IS the delegated object's contract: payload/response
    # fields, which fields each op writes (elision metadata), and the
    # key→owner routing rule (the counter lives on trustee 0)
    counter_schema = TrustSchema(
        "counter",
        ops=[OpSpec("inc",
                    payload=[Field("delta", (), jnp.float32)],
                    response=[Field("value", (), jnp.float32)],
                    writes=["value"], serve=inc)],
        state={"ct": Field("ct", (), jnp.float32)},
        route=lambda payload, t: jnp.zeros_like(payload["delta"],
                                                dtype=jnp.int32))

    group = TrusteeGroup(mesh, ("data", "model"))
    # one counter slot per trustee (state leading dim must divide over the
    # group); trustee 0 owns the counter — the schema routes every request
    ct0 = jnp.zeros((group.n_trustees,)).at[0].set(17.0)
    trust = group.entrust({"ct": ct0}, schema=counter_schema, capacity=8)
    trust.op.inc(jnp.ones((2,)))                 # typed, routed apply()
    out = trust.op.inc(jnp.zeros((1,)))
    print(f"counter value: {float(out['value'][0])}  (paper asserts 19)")
    assert float(out["value"][0]) == 19.0

    # a bad argument raises BEFORE any channel round — the submit-time
    # type check the stringly API never had
    try:
        trust.op.inc(jnp.zeros((2, 3), jnp.int32))
    except SchemaError as e:
        print(f"typed API rejected a bad batch: {e}")
    else:
        raise AssertionError("SchemaError not raised for a bad batch")

    # --- Fig. 3: apply_then — async delegation with a then-callback --------
    got = []
    fut = trust.op.inc.then(jnp.ones((1,)),
                            then=lambda r: got.append(float(r["value"][0])))
    trust.flush()
    print(f"async then-callback saw counter = {got[0]}")

    # --- the KV store (paper §6.3) in three lines ---------------------------
    store = DelegatedKVStore(mesh, n_keys=1024, value_width=4)
    store.put(jnp.arange(8), jnp.tile(jnp.arange(8.0)[:, None], (1, 4)))
    print("GET [3, 5] ->", np.asarray(store.get(jnp.array([3, 5]))[:, 0]))

    # fetch-and-add, the paper's microbenchmark op — the facade above is a
    # thin veneer over the same typed handles:
    old = store.trust.op.add(jnp.array([3, 3, 3]), jnp.ones((3, 4)))
    print("three racing fetch-and-adds on key 3 returned (FIFO):",
          np.asarray(old["value"][:, 0]))

    # --- the session engine: ONE round for ALL trusts (DESIGN.md §8) --------
    # every entrusted object registers with the ambient TrustSession;
    # session.step() fuses all pending submits — here the KV store and a
    # second counters table — into a single multiplexed channel round (one
    # request all_to_all + one response transpose for everything)
    session = current_session()
    counters = DelegatedKVStore(mesh, n_keys=64, value_width=4,
                                name="counters")
    got = store.trust.op.get.then(jnp.array([3, 5]))
    counters.trust.op.put.then(jnp.arange(4), jnp.ones((4, 4)))
    bumped = counters.trust.op.add.then(jnp.arange(4), jnp.ones((4, 4)))
    session.step()              # ONE fused round serves both trusts
    print("fused-round GET [3, 5] ->", np.asarray(got.result()["value"][:, 0]))
    print("fused-round counters ->",
          np.asarray(bumped.result()["value"][:, 0]))
    print("engine stats:", session.last_stats())

    # --- dedicated mode: reserved trustee cores (paper's second runtime) ----
    # needs >= 2 devices: the trailing cores hold the table and serve the
    # rest; the client API is unchanged
    if mesh.size >= 2:
        ded = DelegatedKVStore(mesh, n_keys=1024, value_width=4,
                               mode="dedicated", n_dedicated=mesh.size // 2)
        ded.put(jnp.arange(8), jnp.tile(jnp.arange(8.0)[:, None], (1, 4)))
        print("dedicated-mode GET [3, 5] ->",
              np.asarray(ded.get(jnp.array([3, 5]))[:, 0]))


if __name__ == "__main__":
    from repro.runtime import enable_compile_cache
    enable_compile_cache()
    main()
