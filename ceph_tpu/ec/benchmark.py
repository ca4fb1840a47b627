"""Erasure-code benchmark harness.

CLI mirror of reference src/test/erasure-code/ceph_erasure_code_benchmark.cc
(flags --plugin/--workload/--size/--iterations/--erasures/--parameter
:47-53; encode loop :156-179; exhaustive decode_erasures verification
:202-243), extended with the stripe-batch dimension that wins the 10x target
(BASELINE.md config #3: 1024-stripe batched encode on one chip).

Usage:
    python -m ceph_tpu.ec.benchmark --plugin jax_rs --workload encode \
        --size $((1024*1024)) --iterations 64 --parameter k=8 --parameter m=4
"""

from __future__ import annotations

import argparse
import itertools
import json
import time

import numpy as np

from ceph_tpu.ec.registry import ErasureCodePluginRegistry


def _parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--plugin", default="jax_rs")
    p.add_argument("--workload", choices=("encode", "decode"), default="encode")
    p.add_argument("--size", type=int, default=1 << 20,
                   help="total bytes per iteration")
    p.add_argument("--iterations", type=int, default=16)
    p.add_argument("--stripes", type=int, default=1024,
                   help="stripe batch per device launch")
    p.add_argument("--erasures", type=int, default=2,
                   help="erasures per decode iteration")
    p.add_argument("--erased", type=int, action="append", default=None,
                   help="explicit chunk ids to erase (repeatable)")
    p.add_argument("--parameter", "-P", action="append", default=[],
                   help="profile key=value (repeatable)")
    p.add_argument("--verify", action="store_true",
                   help="exhaustively verify all erasure combinations "
                        "(decode_erasures sweep)")
    p.add_argument("--json", action="store_true", help="emit one JSON line")
    p.add_argument("--profile", metavar="DIR", default=None,
                   help="wrap the run in jax.profiler.trace(DIR) — "
                        "inspect with tensorboard/xprof")
    return p.parse_args(argv)


def device_seconds_per_iter(step, x0, lo: int = 100, hi: int = 300,
                            trials: int = 3) -> float:
    """Per-iteration device time for ``x = step(i, x)``.

    Runs the step serially inside one jitted ``fori_loop`` (the carry
    makes iterations data-dependent, so nothing can be overlapped,
    cached, or hoisted), forces a one-element fetch, and differences two
    iteration counts so fixed costs (dispatch, fetch, loop entry)
    cancel; the median of ``trials`` differences is returned.  Which
    timing protocol the benchmark should use on the chip is left to the
    benchmark work (ROADMAP S1).
    """
    import jax
    import jax.numpy as jnp

    # The trip count is a TRACED argument: fori_loop lowers to a
    # while_loop and one compiled program serves every (lo, hi) pair —
    # including the widening retries below, which would otherwise each
    # pay a fresh compile for their new static count.
    @jax.jit
    def loop(x, n):
        return jax.lax.fori_loop(0, n, step, x)

    def run(n: int) -> float:
        t0 = time.perf_counter()
        out = loop(x0, jnp.int32(n))
        leaf = jax.tree_util.tree_leaves(out)[0]
        np.asarray(leaf.ravel()[0])  # 1-element fetch forces completion
        return time.perf_counter() - t0

    run(lo), run(hi)  # compile once + warm the fetch path
    for _ in range(3):
        samples = sorted(
            (run(hi) - run(lo)) / (hi - lo) for _ in range(trials)
        )
        est = samples[len(samples) // 2]  # median rides out host hiccups
        if est > 0:
            return est
        # A hiccup during a lo run can flip the diff negative; widen the
        # spread so real per-iteration time dominates and retry (bounded).
        lo, hi = hi, hi * 4
        run(hi)  # warm the new count (no recompile: n is traced)
    raise RuntimeError(
        "device timing did not stabilise: per-iteration cost is below "
        "measurement noise even at %d iterations" % hi
    )


def make_codec(plugin: str, parameters: list[str]):
    profile = {}
    for kv in parameters:
        key, _, val = kv.partition("=")
        profile[key] = val
    registry = ErasureCodePluginRegistry()
    return registry.factory(plugin, profile)


def _shard_words(data: np.ndarray):
    """(stripes, k, C) uint8 host batch -> (k, stripes*C/4) int32 device."""
    import jax.numpy as jnp

    from ceph_tpu.ec.pallas_kernels import bytes_to_words

    stripes, k, C = data.shape
    stream = np.ascontiguousarray(
        np.transpose(data, (1, 0, 2)).reshape(k, stripes * C)
    )
    return bytes_to_words(jnp.asarray(stream))


def run_encode(ec, size: int, iterations: int, stripes: int) -> dict:
    """Device-resident shard-stream encode throughput (the HBM analog of
    the reference benchmark's RAM-resident bufferlists), timed with the
    serial-loop protocol of device_seconds_per_iter."""
    k = ec.get_data_chunk_count()
    chunk = ec.get_chunk_size(max(size // max(stripes, 1), 1))
    data = np.random.default_rng(0).integers(
        0, 256, (stripes, k, chunk), dtype=np.uint8
    )
    if getattr(ec, "full_bm", None) is not None:
        # Packet codecs (bit-schedule / wide-symbol): device-resident
        # stripe batch through encode_chunks_device (the apply_packets
        # shard-kernel path), same serial-loop protocol.
        import jax.numpy as jnp

        k_ = ec.get_data_chunk_count()
        dev = jnp.asarray(data)

        def step(i, d):
            out = ec.encode_chunks_device(d)
            return d.at[0, 0, 0].set(out[0, k_, 0] ^ i.astype(jnp.uint8))

        lo = max(iterations // 4, 2)
        sec = device_seconds_per_iter(step, dev, lo=lo, hi=iterations + lo)
        return {
            "workload": "encode", "bytes": data.nbytes, "seconds": sec,
            "GiBps": data.nbytes / sec / 2**30, "chunk_size": chunk,
            "stripes": stripes, "path": "device-packets",
        }
    if not hasattr(ec, "encode_words_device"):
        # Host-path plugins (lrc/shec/clay orchestration): wall-clock the
        # batch API; results materialize on the host so timing is honest.
        np.asarray(ec.encode_chunks_batch(data))  # warm jit compiles
        t0 = time.perf_counter()
        for _ in range(max(iterations // 8, 1)):
            np.asarray(ec.encode_chunks_batch(data))
        dt = time.perf_counter() - t0
        total = data.nbytes * max(iterations // 8, 1)
        return {
            "workload": "encode", "bytes": total, "seconds": dt,
            "GiBps": total / dt / 2**30, "chunk_size": chunk,
            "stripes": stripes, "path": "host",
        }
    words = _shard_words(data)

    def step(i, w):
        p = ec.encode_words_device(w)
        return w.at[0, 0].set(p[0, 0] ^ i)

    lo = max(iterations // 4, 2)
    sec = device_seconds_per_iter(step, words, lo=lo, hi=iterations + lo)
    return {
        "workload": "encode",
        "bytes": data.nbytes,
        "seconds": sec,
        "GiBps": data.nbytes / sec / 2**30,
        "chunk_size": chunk,
        "stripes": stripes,
        "path": "device-words",
    }


def run_decode(ec, size: int, iterations: int, stripes: int,
               erasures: int, erased=None) -> dict:
    import jax

    k = ec.get_data_chunk_count()
    n = ec.get_chunk_count()
    chunk = ec.get_chunk_size(max(size // max(stripes, 1), 1))
    data = np.random.default_rng(0).integers(
        0, 256, (stripes, k, chunk), dtype=np.uint8
    )
    lost = list(erased) if erased else list(range(min(erasures, n)))
    if getattr(ec, "full_bm", None) is not None:
        # Packet codecs: device-resident survivors, decode_chunks_device
        # (apply_packets shard-kernel path).
        import jax.numpy as jnp

        chunks = ec.encode_chunks_device(jnp.asarray(data))
        avail = {i: chunks[:, i] for i in range(n) if i not in lost}

        def step(i, av):
            out = ec.decode_chunks_device(
                {cid: av[j] for j, cid in enumerate(sorted(avail))}, lost
            )
            return av.at[0, 0, 0].set(out[0, 0, 0] ^ i.astype(jnp.uint8))

        stacked = jnp.stack([avail[cid] for cid in sorted(avail)], axis=0)
        lo = max(iterations // 4, 2)
        sec = device_seconds_per_iter(step, stacked, lo=lo,
                                      hi=iterations + lo)
        return {
            "workload": "decode", "bytes": data.nbytes, "seconds": sec,
            "GiBps": data.nbytes / sec / 2**30, "erased": lost,
            "chunk_size": chunk, "stripes": stripes,
            "path": "device-packets",
        }
    if not hasattr(ec, "encode_words_device"):
        chunks = np.asarray(ec.encode_chunks_batch(data))
        avail = {i: chunks[:, i] for i in range(n) if i not in lost}
        for v in ec.decode_chunks_batch(avail, lost).values():
            np.asarray(v)  # warm jit compiles
        t0 = time.perf_counter()
        for _ in range(max(iterations // 8, 1)):
            out = ec.decode_chunks_batch(avail, lost)
            for v in out.values():
                np.asarray(v)
        dt = time.perf_counter() - t0
        total = data.nbytes * max(iterations // 8, 1)
        return {
            "workload": "decode", "bytes": total, "seconds": dt,
            "GiBps": total / dt / 2**30, "erased": lost,
            "chunk_size": chunk, "stripes": stripes, "path": "host",
        }
    words = _shard_words(data)
    enc = jax.block_until_ready(ec.encode_words_device(words))
    full = jax.numpy.concatenate([words, enc], axis=0)  # (k+m, N4)
    avail_ids = [i for i in range(n) if i not in lost][:k]
    surv = full[jax.numpy.asarray(avail_ids)]

    def step(i, s):
        rec = ec.decode_words_device(
            {a: s[j] for j, a in enumerate(avail_ids)}, lost
        )
        return s.at[0, 0].set(rec[0, 0] ^ i)

    lo = max(iterations // 4, 2)
    sec = device_seconds_per_iter(step, surv, lo=lo, hi=iterations + lo)
    return {
        "workload": "decode",
        "bytes": data.nbytes,
        "seconds": sec,
        "GiBps": data.nbytes / sec / 2**30,
        "erased": lost,
        "chunk_size": chunk,
        "stripes": stripes,
        "path": "device-words",
    }


def verify_all_erasures(ec, size: int = 4096) -> int:
    """Exhaustive erasure sweep — every combination of up to m lost chunks
    must reconstruct bit-identically (benchmark.cc:202-243 semantics).
    Returns the number of combinations checked."""
    k, n = ec.get_data_chunk_count(), ec.get_chunk_count()
    m = n - k
    payload = np.random.default_rng(1).integers(0, 256, size, np.uint8).tobytes()
    enc = ec.encode(list(range(n)), payload)
    checked = 0
    for r in range(1, m + 1):
        for lost in itertools.combinations(range(n), r):
            avail = {i: enc[i] for i in range(n) if i not in lost}
            # Non-MDS codes (lrc, shec) cannot recover every combination;
            # minimum_to_decode is the feasibility oracle — when it reports
            # EIO the decode must fail too, never silently corrupt.
            try:
                ec.minimum_to_decode(list(lost), list(avail))
            except IOError:
                try:
                    out = ec.decode(list(lost), avail)
                except IOError:
                    continue
                raise AssertionError(
                    f"minimum_to_decode says lost={lost} is unrecoverable "
                    "but decode succeeded"
                )
            out = ec.decode(list(lost), avail)
            for w in lost:
                if out[w] != enc[w]:
                    raise AssertionError(f"mismatch: lost={lost} chunk={w}")
            checked += 1
    return checked


def main(argv=None) -> dict:
    args = _parse_args(argv)
    ec = make_codec(args.plugin, args.parameter)
    profiler = None
    if args.profile:
        import jax.profiler as profiler

        profiler.start_trace(args.profile)
    try:
        if args.verify:
            n = verify_all_erasures(ec)
            result = {"workload": "verify", "combinations": n,
                      "ok": True}
        elif args.workload == "encode":
            result = run_encode(ec, args.size, args.iterations,
                                args.stripes)
        else:
            result = run_decode(
                ec, args.size, args.iterations, args.stripes,
                args.erasures, args.erased,
            )
    finally:
        if profiler is not None:
            profiler.stop_trace()
    result["plugin"] = args.plugin
    result["profile"] = ec.get_profile()
    print(json.dumps(result) if args.json else result)
    return result


if __name__ == "__main__":
    main()
