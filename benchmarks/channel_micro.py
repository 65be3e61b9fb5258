"""Channel microbenchmarks — paper §5 design points.

  * slot-capacity sweep (the 1152-byte slot / two-part trade-off, §5.3.1):
    primary capacity vs. served fraction vs. round time.
  * local-trustee shortcut on/off (§5.2.1).
  * overflow mode: drop vs second_round vs defer (drain engine).
  * pack implementation: lax reference vs the MXU Pallas pack kernel
    (interpret mode off-TPU), same channel round either way.
  * engine_multi: one MULTIPLEXED engine round serving two Trusts (KV table
    + ledger) vs one solo channel round per Trust (DESIGN.md §8) — the
    fused round pays one program dispatch and one all_to_all pair where the
    per-trust path pays two of each.
  * serve_hotpath: the trustee serve path (DESIGN.md §9) across op mixes —
    GET-heavy / PUT-heavy / mixed / conflict-heavy fused rounds served by
    the legacy masked per-op passes vs the shared-grouping segment
    primitives vs the fused Pallas serve kernel; PUT-heavy rows also record
    the response-transpose bytes the elision plan drops.
  * api_overhead: typed-handle dispatch (schema binding + routing,
    DESIGN.md §10) vs the raw stringly apply over the same compiled
    program — the CI-gated typed/raw within-run ratio.
  * serve_scale: the DIRECT serve path (no mesh round-trip) over a row-
    batch sweep — masked vs shared-grouping ref at every R, the tiled
    Pallas serve (interpret mode off-TPU) up to --scale-pallas-max-r.
    The CI gate tracks the within-run ref/masked ratio at r8192/r32768
    (check_bench --normalize-impl masked) so the shared-grouping serve
    cannot silently lose its scaling edge; the kernel's scaling numbers
    come from benchmarks/kernel_sweep.py on a TPU.
  * combine: pre-wire request combining (DESIGN.md §13) over a Zipf skew
    sweep plus the 16-key conflict-heavy mix, combine{off,ref} under a
    pressured defer drain — the combine mode rides the pack_impl column
    so check_bench gates the within-run ref/off ops ratio.

Every row carries ``dup_factor`` — requests per distinct (op, key) pair
in the wave, the combining headroom of the trace (1.0 where the trace is
not key-addressed or not recorded).
"""
from __future__ import annotations

import argparse

import numpy as np


def dup_factor(batches) -> float:
    """Requests per distinct (op, key) pair across a wave's batches — the
    per-round combining headroom of the trace (1.0 = every pair unique)."""
    pairs, total = set(), 0
    for op, keys, _vals, _expect in batches:
        ks = np.asarray(keys).ravel()
        total += ks.size
        pairs.update((op, int(k)) for k in ks)
    return round(total / max(1, len(pairs)), 2)


def combine_exp(csv, mesh, args):
    """Request combining (DESIGN.md §13): combine{off,ref} over identical
    traces.  Skewed settings sweep Zipf alpha on 4096 keys; conflict_heavy
    squeezes the wave onto 16 keys and shrinks the slot block so the defer
    drain needs several rounds WITHOUT combining and one round WITH it —
    the honest way the wire-row reduction becomes wall-clock on any
    backend (with ample slots the padded all_to_all is the same size
    either way).  The combine mode rides the pack_impl column so the CI
    gate (check_bench --impl ref --normalize-impl off) tracks the
    within-run on/off ops ratio; CAS is excluded from the mix because it
    is the uncombinable archetype (expect/swap is order-sensitive)."""
    import jax.numpy as jnp
    from repro.core import DelegatedKVStore
    from repro.core.routing import sample_keys
    from benchmarks.common import bench, block

    R = args.requests
    n_dev = mesh.size
    # setting -> (n_keys, dist, alpha, pressured)
    settings = {
        "uniform": (4096, "uniform", 1.0, False),
        "zipf0.8": (4096, "zipf", 0.8, False),
        "zipf1.1": (4096, "zipf", 1.1, False),
        "zipf1.4": (4096, "zipf", 1.4, False),
        "conflict_heavy": (16, "zipf", 1.1, True),
    }
    parts = [("get", 0.4), ("put", 0.2), ("add", 0.4)]
    for setting, (n_keys, dist, alpha, pressured) in settings.items():
        rng = np.random.default_rng(29)
        batches = []
        for op, frac in parts:
            n = max(1, int(R * frac))
            keys = jnp.asarray(sample_keys(rng, n_keys, n, dist, alpha))
            vals = jnp.asarray(
                rng.integers(0, 8, (n, 1)).astype(np.float32))
            batches.append((op, keys, vals, None))
        dup = dup_factor(batches)
        for mode in ("off", "ref"):
            kw = dict(capacity=max(1, R // n_dev), local_shortcut=False,
                      combine=mode)
            if pressured:
                # tight primary block + bounded drain: combine-off pays
                # extra rounds for the hot trustee, combine-on collapses
                # each shard to <= |ops| x |local keys| segments per round
                kw.update(capacity=max(1, R // n_dev // 16),
                          overflow="defer", max_rounds=64)
            st = DelegatedKVStore(mesh, n_keys, 1,
                                  name=f"kv_{setting}_{mode}", **kw)
            st.prefill(np.zeros((n_keys, 1), np.float32))

            def wave():
                futs = []
                for op, keys, vals, _ in batches:
                    if op == "get":
                        futs.append(st.get_then(keys))
                    elif op == "put":
                        st.put_then(keys, vals)
                    else:
                        futs.append(st.add_then(keys, vals))
                st.flush()
                block([f.result()["value"] for f in futs]
                      + [st.trust.state()["table"]])

            wave()
            stats = st.session.last_stats()[st.trust.name]
            combined = int(stats.get("rows_combined", 0))
            saved = int(stats.get("req_bytes_saved", 0))
            print(f"combine {setting} {mode}: rows_combined={combined} "
                  f"req_bytes_saved={saved}", flush=True)
            dt = bench(wave, iters=4)
            csv.add("combine", setting, mode, round(dt * 1e6, 1), 1.0, dup)


def serve_hotpath(csv, mesh, args):
    """One fused multi-op round per wave, identical trace per serve impl."""
    import jax.numpy as jnp
    from repro.core import DelegatedKVStore
    from repro.core.routing import sample_keys
    from benchmarks.common import bench, block

    R = args.requests
    mixes = {
        # (n_keys, [(op, fraction), ...]) — conflict_heavy squeezes the
        # whole request wave onto 16 keys (every segment is deep);
        # put_only elides the ENTIRE response transpose (the paper's
        # zero-size PUT response, applied statically)
        "get_heavy": (4096, [("get", 0.8), ("put", 0.2)]),
        "put_heavy": (4096, [("put", 0.9), ("get", 0.1)]),
        "put_only": (4096, [("put", 1.0)]),
        "mixed": (4096, [("get", 0.25), ("put", 0.25),
                         ("add", 0.25), ("cas", 0.25)]),
        "conflict_heavy": (16, [("get", 0.25), ("put", 0.25),
                                ("add", 0.25), ("cas", 0.25)]),
    }
    n_dev = mesh.size
    for mix_name, (n_keys, parts) in mixes.items():
        rng = np.random.default_rng(17)
        batches = []
        for op, frac in parts:
            n = max(1, int(R * frac))
            keys = jnp.asarray(sample_keys(rng, n_keys, n, "zipf"))
            vals = jnp.asarray(
                rng.integers(0, 8, (n, 1)).astype(np.float32))
            expect = jnp.asarray(
                rng.integers(0, 8, (n, 1)).astype(np.float32))
            batches.append((op, keys, vals, expect))
        dup = dup_factor(batches)
        for impl in ("masked", "ref", "pallas"):
            st = DelegatedKVStore(mesh, n_keys, 1,
                                  capacity=max(1, R // n_dev),
                                  serve_impl=impl, local_shortcut=False)
            st.prefill(np.zeros((n_keys, 1), np.float32))

            def wave():
                futs = []
                for op, keys, vals, expect in batches:
                    if op == "get":
                        futs.append(st.get_then(keys))
                    elif op == "put":
                        st.put_then(keys, vals)
                    elif op == "add":
                        futs.append(st.add_then(keys, vals))
                    else:
                        futs.append(st.trust.op.cas.then(
                            keys, value=vals, expect=expect))
                st.flush()
                block([f.result()["value"] for f in futs]
                      + [st.trust.state()["table"]])

            wave()
            saved = st.session.last_stats()[st.trust.name] \
                .get("resp_bytes_saved", 0)
            dt = bench(wave, iters=4)
            csv.add("serve_hotpath", f"{mix_name}_elide{saved}", impl,
                    round(dt * 1e6, 1), 1.0, dup)


def serve_scale(csv, mesh, args):
    """Serve-path scaling: one fused mixed-op batch served DIRECTLY via
    serve_optable (single shard, no channel round) at growing row counts.
    This is the sweep the tiled kernels exist for — the retired dense
    kernel's (N, N) masks made R past a few thousand unrunnable."""
    import jax
    import jax.numpy as jnp
    from repro.core import Received, make_kv_ops, serve_optable
    from repro.core.routing import sample_keys
    from benchmarks.common import bench, block

    n_keys, vw = 4096, 2
    ops = make_kv_ops(1, vw)
    rs = [int(x) for x in args.scale_rs.split(",") if x]
    for r in rs:
        rng = np.random.default_rng(11)
        rows = {"op": jnp.asarray(rng.integers(0, 4, r).astype(np.int16)),
                "key": jnp.asarray(sample_keys(rng, n_keys, r, "zipf")),
                "value": jnp.asarray(
                    rng.integers(0, 8, (r, vw)).astype(np.float32)),
                "expect": jnp.asarray(
                    rng.integers(0, 8, (r, vw)).astype(np.float32))}
        received = Received(rows, jnp.ones((r,), bool),
                            jnp.zeros((r,), jnp.int32))
        dup = round(r / max(1, len(set(
            zip(np.asarray(rows["op"]).tolist(),
                np.asarray(rows["key"]).tolist())))), 2)
        state = {"table": jnp.asarray(
            rng.integers(0, 8, (n_keys, vw)).astype(np.float32))}
        impls = ["masked", "ref"]
        # interpret-mode Pallas executes the grid in Python-built XLA loops:
        # honest on semantics, useless on wall-clock past a few 10k rows —
        # the uninterpreted sweep is benchmarks/kernel_sweep.py on a TPU
        if r <= args.scale_pallas_max_r:
            impls.append("pallas")
        for impl in impls:
            serve = jax.jit(serve_optable(ops, active_ids=(0, 1, 2, 3),
                                          serve_impl=impl))

            def round_():
                new_state, resp = serve(state, received)
                block((new_state["table"], resp["value"]))

            dt = bench(round_, iters=4)
            csv.add("serve_scale", f"r{r}", impl, round(dt * 1e6, 1), 1.0,
                    dup)


def api_overhead(csv, mesh, args):
    """Typed-handle dispatch vs the raw stringly apply (DESIGN.md §10).

    SAME trust, SAME compiled program — the engine cache key is shared by
    both paths (schema identity) — so the measured delta is pure host-side
    dispatch: handle binding + schema routing vs a hand-built dst/payload.
    The CI gate tracks the within-run typed/raw ratio (check_bench
    --normalize-impl raw) so the typed surface cannot silently grow a
    dispatch tax."""
    import jax.numpy as jnp
    from repro.core import DelegatedKVStore
    from repro.core.routing import sample_keys
    from benchmarks.common import block

    R = args.requests
    n_keys = 4096
    rng = np.random.default_rng(23)
    keys = jnp.asarray(sample_keys(rng, n_keys, R, "zipf"))
    ones = jnp.ones((R, 1), jnp.float32)
    st = DelegatedKVStore(mesh, n_keys, 1, capacity=max(1, R // mesh.size),
                          local_shortcut=False)
    st.prefill(np.zeros((n_keys, 1), np.float32))
    dst = st.route(keys)
    payload_get = {"key": keys.astype(jnp.int32)}
    payload_add = {"key": keys.astype(jnp.int32), "value": ones}

    def raw_get():
        block(st.trust.apply("get", dst, payload_get)["value"])

    def typed_get():
        block(st.trust.op.get(keys)["value"])

    def raw_wave():
        g = st.trust.submit("get", dst, payload_get)
        a = st.trust.submit("add", dst, payload_add)
        st.flush()
        block((g.result()["value"], a.result()["value"]))

    def typed_wave():
        g = st.trust.op.get.then(keys)
        a = st.trust.op.add.then(keys, ones)
        st.flush()
        block((g.result()["value"], a.result()["value"]))

    # the gated metric is the typed/raw ratio, so the two impls of one
    # setting are timed INTERLEAVED (alternating calls): ms-scale container
    # drift then hits both alike instead of whichever phase ran second
    # (the plain sequential bench() flapped 2-5x here).  The estimator is
    # the MIN over the interleaved iterations — the noise on this box is
    # strictly additive (scheduler stalls), so min is the stable
    # dispatch-cost estimate the ratio gate needs.
    import time as _time
    for setting, impls in (("get_solo", (("raw", raw_get),
                                         ("typed", typed_get))),
                           ("mixed_wave", (("raw", raw_wave),
                                           ("typed", typed_wave)))):
        for _impl, fn in impls:
            fn(); fn()                      # shared-program warmup/compile
        times = {impl: [] for impl, _fn in impls}
        for _ in range(21):
            for impl, fn in impls:
                t0 = _time.perf_counter()
                fn()
                times[impl].append(_time.perf_counter() - t0)
        for impl, ts in times.items():
            csv.add("api_overhead", setting, impl,
                    round(min(ts) * 1e6, 1), 1.0, 1.0)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=4096)
    ap.add_argument("--pack-impl", default="both",
                    choices=["ref", "pallas", "both"],
                    help="channel pack path for the pack_impl experiment; "
                         "'both' emits one row per implementation")
    ap.add_argument("--drain-rounds", type=int, default=8,
                    help="defer drain-engine round bound for the "
                         "defer_drain experiment")
    ap.add_argument("--experiment", default="",
                    help="run only experiments whose name contains this "
                         "substring (e.g. serve_hotpath for the CI "
                         "bench-smoke job)")
    ap.add_argument("--scale-rs", default="8192,16384,32768,65536",
                    help="serve_scale row-batch sweep (comma-separated)")
    ap.add_argument("--scale-pallas-max-r", type=int, default=8192,
                    help="serve_scale: largest R for the interpret-mode "
                         "Pallas serve (lax impls run the full sweep)")
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh
    from repro.core import DelegatedKVStore
    from repro.core.routing import sample_keys
    from benchmarks.common import Csv, bench, block

    n_dev = len(jax.devices())
    mesh = Mesh(np.array(jax.devices()).reshape(1, n_dev), ("data", "model"))
    R = args.requests
    n_keys = 4096
    rng = np.random.default_rng(5)
    keys_np = sample_keys(rng, n_keys, R, "zipf")
    keys = jnp.asarray(keys_np)
    ones = jnp.ones((R, 1), jnp.float32)
    mean_cap = max(1, R // n_dev // n_dev)
    # the shared add-wave trace below is single-op: its dup factor is
    # requests per distinct key
    dup_main = round(R / max(1, len(set(keys_np.tolist()))), 2)

    csv = Csv(["experiment", "setting", "pack_impl", "us_per_round",
               "served_frac", "dup_factor"])
    csv.print_header()

    # --experiment names ONE experiment to run alone (CI bench-smoke uses
    # serve_hotpath, the api-overhead gate api_overhead, the combining
    # gate combine); only experiments that can run standalone are
    # filterable
    filterable = ("serve_hotpath", "api_overhead", "serve_scale", "combine")
    if args.experiment and args.experiment not in filterable:
        ap.error(f"--experiment must be one of {filterable}, "
                 f"got {args.experiment!r}")
    if not args.experiment or args.experiment == "serve_hotpath":
        serve_hotpath(csv, mesh, args)
    if not args.experiment or args.experiment == "api_overhead":
        api_overhead(csv, mesh, args)
    if not args.experiment or args.experiment == "combine":
        combine_exp(csv, mesh, args)
    # serve_scale is opt-in only (the sweep dwarfs the default suite)
    if args.experiment == "serve_scale":
        serve_scale(csv, mesh, args)
    if args.experiment:
        if args.out:
            csv.dump(args.out)
        return

    # capacity sweep, drop mode (how big must the primary block be?)
    for mult in (0.5, 1, 2, 4, 8):
        cap = max(1, int(mean_cap * mult))
        st = DelegatedKVStore(mesh, n_keys, 1, capacity=cap, overflow="drop",
                              local_shortcut=False)
        st.prefill(np.zeros((n_keys, 1), np.float32))
        out = st.add(keys, ones)
        served = float((np.asarray(out) != 0).any(1).mean())
        dt = bench(lambda: block(st.add(keys, ones)), iters=4)
        csv.add("capacity_drop", f"{mult}x_mean", "ref", round(dt * 1e6, 1),
                round(served, 4), dup_main)

    # two-part slot: small primary + overflow round (lossless)
    for mult in (0.5, 1, 2):
        cap = max(1, int(mean_cap * mult))
        st = DelegatedKVStore(mesh, n_keys, 1, capacity=cap,
                              overflow="second_round",
                              overflow_capacity=cap * 4, local_shortcut=False)
        st.prefill(np.zeros((n_keys, 1), np.float32))
        out = st.add(keys, ones)
        served = float((np.asarray(out) != 0).any(1).mean())
        dt = bench(lambda: block(st.add(keys, ones)), iters=4)
        csv.add("two_part_slot", f"{mult}x_mean+4x_overflow", "ref",
                round(dt * 1e6, 1), round(served, 4), dup_main)

    # defer + drain engine: bounded multi-round backpressure (paper §5.1
    # wait-for-slot) — small primary blocks drain losslessly over rounds
    for mult in (0.25, 0.5, 1):
        cap = max(1, int(mean_cap * mult))
        st = DelegatedKVStore(mesh, n_keys, 1, capacity=cap, overflow="defer",
                              max_rounds=args.drain_rounds,
                              local_shortcut=False)
        st.prefill(np.zeros((n_keys, 1), np.float32))
        block(st.add(keys, ones))
        stats = st.trust.last_drain_stats()
        served = 1.0 - stats["residual"] / R
        dt = bench(lambda: block(st.add(keys, ones)), iters=4)
        csv.add("defer_drain", f"{mult}x_mean_r{stats['rounds']}", "ref",
                round(dt * 1e6, 1), round(served, 4), dup_main)

    # local shortcut ablation
    for shortcut in (False, True):
        st = DelegatedKVStore(mesh, n_keys, 1, capacity=8 * mean_cap,
                              local_shortcut=shortcut)
        st.prefill(np.zeros((n_keys, 1), np.float32))
        dt = bench(lambda: block(st.add(keys, ones)), iters=4)
        csv.add("local_shortcut", str(shortcut), "ref", round(dt * 1e6, 1),
                1.0, dup_main)

    # pack implementation: lax reference vs Pallas MXU kernel, same round
    impls = (["ref", "pallas"] if args.pack_impl == "both"
             else [args.pack_impl])
    for impl in impls:
        st = DelegatedKVStore(mesh, n_keys, 1, capacity=2 * mean_cap,
                              pack_impl=impl, local_shortcut=False)
        st.prefill(np.zeros((n_keys, 1), np.float32))
        dt = bench(lambda: block(st.add(keys, ones)), iters=4)
        csv.add("pack_impl", f"cap2x_{impl}", impl, round(dt * 1e6, 1), 1.0,
                dup_main)

    # engine_multi: TWO Trusts (KV table + token ledger) per request wave —
    # one multiplexed session.step() vs one solo round per Trust.  Same
    # channel config either way; responses are block()ed so each setting
    # pays its full dispatch + collective cost.
    from repro.core import TrustSession
    ses = TrustSession()
    eng_impl = args.pack_impl if args.pack_impl in ("ref", "pallas") else "ref"
    kw = dict(capacity=8 * mean_cap, local_shortcut=False,
              pack_impl=eng_impl)
    kv = DelegatedKVStore(mesh, n_keys, 1, session=ses, name="kv", **kw)
    led = DelegatedKVStore(mesh, n_keys, 1, session=ses, name="ledger", **kw)
    keys_b = jnp.asarray(sample_keys(rng, n_keys, R, "zipf"))
    for st in (kv, led):
        st.prefill(np.ones((n_keys, 1), np.float32))

    def per_trust():
        a = kv.add(keys, ones)
        b = led.add(keys_b, ones)
        block((a, b))
        return a, b

    def fused():
        fa = kv.add_then(keys, ones)
        fb = led.add_then(keys_b, ones)
        ses.step()
        block((fa.result()["value"], fb.result()["value"]))
        return fa.result()["value"], fb.result()["value"]

    out_a, out_b = fused()
    served = float(np.mean([(np.asarray(out_a) != 0).any(1).mean(),
                            (np.asarray(out_b) != 0).any(1).mean()]))
    for setting, fn in (("per_trust", per_trust), ("fused", fused)):
        dt = bench(fn, iters=4)
        csv.add("engine_multi", setting, eng_impl,
                round(dt * 1e6, 1), round(served, 4), dup_main)

    if args.out:
        csv.dump(args.out)


if __name__ == "__main__":
    from repro.runtime import enable_compile_cache
    enable_compile_cache()
    main()
