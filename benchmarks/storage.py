"""Storage-engine benchmark: persisted shredded datasets vs in-process
regeneration, pruned vs full scans, and zone-map skip rates.

Measured (all over the nested TPC-H-like generator):

  * ``storage_generate``   — regenerate + value-shred in memory (what
    every process start paid before the storage engine);
  * ``storage_cold_load``  — open the persisted dataset and load every
    part (the replacement for regeneration), with ``bytes_on_disk``;
  * ``storage_full_scan``  / ``storage_pruned_scan`` — full load vs a
    compiled query's column-pruned + zone-map-skipped load, with
    ``chunks_skipped`` and bytes read;
  * ``storage_skip_rate``  — chunk skip fraction as the pushed-down
    ``N.Param`` price threshold sweeps the selectivity range, under ONE
    warm ``QueryService`` plan (zero retraces asserted in smoke mode);
  * ``storage_compressed_footprint`` / ``storage_label_cold_scan_*`` —
    raw vs auto-encoded datasets: bytes on disk, compression ratio,
    and the cold (page-cache-evicted) scan of the RLE-friendly sorted
    label column, with decode GB/s and the bytes_read (disk) vs
    bytes_decoded (logical) split;
  * ``storage_morsel_stream`` — the out-of-core morsel-streamed query
    vs the one-shot stored path: bit-for-bit parity, morsel count,
    peak resident rows vs full-part rows, zero warm retraces.

Smoke mode (``--smoke`` / ``make ci storage-smoke``) shrinks sizes and
hard-asserts the storage invariants: write -> reopen -> query parity
with the in-memory path, >=1 chunk skipped on a selective parameter,
and zero warm retracing while chunk selection changes.
``--compress-smoke`` (``make compress-smoke``) asserts the compressed
tier: label-column compression >= 2x, decode parity with raw, chunk
skipping without decode, and a >= 4-morsel stream with zero retraces.
"""

from __future__ import annotations

import argparse
import os
import shutil
import tempfile
import time

import numpy as np

from repro.core import codegen as CG
from repro.core import nrc as N
from repro.core.unnesting import Catalog
from repro.obs import TRACER, tracing
from repro.serve import QueryService
from repro.storage import (STORAGE_STATS, StorageCatalog,
                           reset_storage_stats, storage_requirements)
from repro.storage.format import chunk_path

from .common import emit, set_section, time_fn

PART_T = N.bag(N.tuple_t(pid=N.INT, pname=N.INT, price=N.REAL,
                         mfgr=N.INT))
ORD_T = N.bag(N.tuple_t(
    odate=N.INT,
    oparts=N.bag(N.tuple_t(pid=N.INT, qty=N.REAL, tax=N.REAL))))
INPUT_TYPES = {"Ord": ORD_T, "Part": PART_T}
CATALOG = Catalog(unique_keys={"Part__F": ("pid",)})


def family(min_price: float) -> N.Program:
    Part = N.Var("Part", PART_T)
    Ord = N.Var("Ord", ORD_T)

    def tops(x):
        inner = N.for_in("op", x.oparts, lambda op:
            N.for_in("p", Part, lambda p:
                N.IfThen(N.BoolOp("&&", op.pid.eq(p.pid),
                                  p.price.ge(N.Const(min_price, N.REAL))),
                         N.Singleton(N.record(pname=p.pname,
                                              total=op.qty * p.price)))))
        return N.SumBy(inner, keys=("pname",), values=("total",))

    q = N.for_in("x", Ord, lambda x: N.Singleton(N.record(
        odate=x.odate, tops=tops(x))))
    return N.Program([N.Assignment("Q", q)])


def gen(n_orders: int, n_parts: int, seed: int = 0):
    rng = np.random.RandomState(seed)
    orders = [{"odate": 20200000 + i,
               "oparts": [{"pid": int(rng.randint(1, n_parts + 1)),
                           "qty": float(rng.randint(1, 5)),
                           "tax": 0.07}
                          for _ in range(rng.randint(0, 6))]}
              for i in range(n_orders)]
    parts = [{"pid": i, "pname": 100 + i, "price": float(i),
              "mfgr": i % 7} for i in range(1, n_parts + 1)]
    return {"Ord": orders, "Part": parts}


def _norm(rows):
    return sorted(
        (r["odate"], tuple(sorted((t["pname"], round(t["total"], 6))
                                  for t in r["tops"])))
        for r in rows)


def gen_wide(n_orders: int, fanout: int, n_parts: int = 512,
             seed: int = 0):
    """MB-scale variant: every order has exactly ``fanout`` children,
    so the child part's label column is long sorted runs (the
    RLE-friendly shape the codecs target)."""
    rng = np.random.RandomState(seed)
    orders = [{"odate": 20200000 + i,
               "oparts": [{"pid": int(rng.randint(1, n_parts + 1)),
                           "qty": float(rng.randint(1, 5)),
                           "tax": 0.07}
                          for _ in range(fanout)]}
              for i in range(n_orders)]
    parts = [{"pid": i, "pname": 100 + i, "price": float(i),
              "mfgr": i % 7} for i in range(1, n_parts + 1)]
    return {"Ord": orders, "Part": parts}


def _decode_ms(load) -> float:
    """Milliseconds in the ``decode`` spans of one traced ``load()``."""
    with tracing(reset=True):
        load()
        ms = sum(sp.dur for sp in TRACER.find("decode")) * 1e3
    TRACER.reset()
    return ms


def _evict(root: str) -> None:
    """Best-effort page-cache eviction under the dataset directory
    (fsync + POSIX_FADV_DONTNEED per file), so repeated scans measure
    COLD reads instead of memory copies."""
    for dp, _, fs in os.walk(root):
        for f in fs:
            p = os.path.join(dp, f)
            try:
                fd = os.open(p, os.O_RDONLY)
                try:
                    os.fsync(fd)
                    os.posix_fadvise(fd, 0, 0, os.POSIX_FADV_DONTNEED)
                finally:
                    os.close(fd)
            except OSError:
                pass


def _col_bytes(ds, part: str, col: str) -> int:
    sp = ds.parts[part]
    return sum(os.path.getsize(chunk_path(ds.dir, part, col, i))
               for i in range(sp.n_chunks))


def _bags_bitwise_equal(a, b) -> bool:
    if set(a.data) != set(b.data):
        return False
    va, vb = np.asarray(a.valid), np.asarray(b.valid)
    for c in a.data:
        xa, xb = np.asarray(a.data[c])[va], np.asarray(b.data[c])[vb]
        if xa.shape != xb.shape or not np.array_equal(
                xa.view(np.uint8), xb.view(np.uint8)):
            return False
    return True


def run_compression(n_orders: int = 16000, fanout: int = 60,
                    chunk_rows: int = 65536, iters: int = 9,
                    smoke: bool = False) -> dict:
    """Compressed vs raw storage: footprint, label-column cold-scan
    time (page cache evicted between runs), decode throughput, and
    bit-for-bit decode parity."""
    tmp = tempfile.mkdtemp(prefix="repro_storage_comp_")
    results = {}
    try:
        data = gen_wide(n_orders, fanout)
        cat = StorageCatalog(tmp)
        ds_raw = cat.write("raw", data, INPUT_TYPES,
                           chunk_rows=chunk_rows, encoding="raw")
        ds_enc = cat.write("enc", data, INPUT_TYPES,
                           chunk_rows=chunk_rows, encoding="auto")
        b_raw, b_enc = ds_raw.bytes_on_disk(), ds_enc.bytes_on_disk()
        ratio = b_raw / max(b_enc, 1)
        child = "Ord__D_oparts"
        lbl_raw = _col_bytes(ds_raw, child, "label")
        lbl_enc = _col_bytes(ds_enc, child, "label")
        lbl_ratio = lbl_raw / max(lbl_enc, 1)
        emit("storage_compressed_footprint", 0.0,
             f"raw={b_raw} label_ratio=x{lbl_ratio:.1f}",
             bytes_on_disk=b_enc, compression_ratio=ratio)
        results["compression_ratio"] = ratio
        results["label_ratio"] = lbl_ratio

        # cold scan of the RLE-friendly columns (the sorted parent-rid
        # label + the low-cardinality tax attribute): decoded bytes
        # dwarf the on-disk run-length blobs
        scan_cols = ["label", "tax"]

        # interleave the two variants so machine-state drift during the
        # measurement hits both equally; report medians
        ts_raw, ts_enc = [], []
        reset_storage_stats()
        for _ in range(iters):
            for name, ds, ts in (("raw", ds_raw, ts_raw),
                                 ("enc", ds_enc, ts_enc)):
                _evict(os.path.join(tmp, name))
                t0 = time.perf_counter()
                ds.parts[child].load(columns=scan_cols)
                ts.append((time.perf_counter() - t0) * 1e6)
        t_raw = sorted(ts_raw)[iters // 2]
        t_enc = sorted(ts_enc)[iters // 2]
        s = dict(STORAGE_STATS)
        # the stats window covered both variants; the decode meters only
        # ever tick on the encoded side
        s["bytes_read"] = sum(
            os.path.getsize(chunk_path(ds_enc.dir, child, c, i))
            for c in scan_cols
            for i in range(ds_enc.parts[child].n_chunks)) * iters
        decode_ms = _decode_ms(lambda: (
            _evict(os.path.join(tmp, "enc")),
            ds_enc.parts[child].load(columns=scan_cols)))
        decode_gbs = (s.get("bytes_decoded", 0) / iters / 1e9) \
            / max(decode_ms / 1e3, 1e-9)
        emit("storage_label_cold_scan_raw", t_raw,
             f"rows={ds_raw.parts[child].rows}",
             bytes_read=sum(_col_bytes(ds_raw, child, c)
                            for c in scan_cols))
        emit("storage_label_cold_scan_enc", t_enc,
             f"x{t_raw / max(t_enc, 1e-9):.2f}_vs_raw "
             f"decode_GBps={decode_gbs:.2f}",
             bytes_read=s.get("bytes_read", 0) // iters,
             bytes_decoded=s.get("bytes_decoded", 0) // iters,
             decode_ms=decode_ms)
        results["cold_scan_speedup"] = t_raw / max(t_enc, 1e-9)

        # decode parity: every column of every part, bit for bit
        env_raw, env_enc = ds_raw.load_env(), ds_enc.load_env()
        parity = all(_bags_bitwise_equal(env_raw[n], env_enc[n])
                     for n in env_raw)
        assert parity, "compressed decode differs from raw"
        results["decode_parity"] = parity
        return results
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def run_streamed(n_orders: int = 2000, n_parts: int = 512,
                 chunk_rows: int = 64, morsel_rows: int = 0,
                 smoke: bool = False) -> dict:
    """Morsel-streamed out-of-core execution vs the one-shot stored
    path: same program, same dataset, windows sized so the stream runs
    >= 4 morsels; asserts bit-for-bit output parity and zero warm
    retraces across morsels."""
    tmp = tempfile.mkdtemp(prefix="repro_storage_morsel_")
    results = {}
    try:
        data = gen(n_orders, n_parts)
        cat = StorageCatalog(tmp)
        ds = cat.write("tpch", data, INPUT_TYPES, chunk_rows=chunk_rows)
        svc = QueryService(INPUT_TYPES, catalog=CATALOG)
        prog = family(float(n_parts // 4))
        morsel_rows = morsel_rows or max(n_orders // 4, 1)

        out1 = svc.execute_stored(prog, ds)
        t_oneshot = time_fn(lambda: svc.execute_stored(prog, ds),
                            warmup=0, iters=1 if smoke else 3)
        CG.reset_trace_stats()
        out2 = svc.execute_stored_streaming(prog, ds,
                                            morsel_rows=morsel_rows,
                                            root="Ord")
        cold = CG.TRACE_STATS.get("traces", 0)
        CG.reset_trace_stats()
        t_stream = time_fn(
            lambda: svc.execute_stored_streaming(
                prog, ds, morsel_rows=morsel_rows, root="Ord"),
            warmup=0, iters=1 if smoke else 3)
        warm = CG.TRACE_STATS.get("traces", 0)

        entry = next(e for e in svc._cache.values() if e.morsel)
        mp = entry.morsel[0]
        peak = max(entry.class_caps[p] for p in mp.parts)
        full = max(ds.parts[p].rows for p in mp.parts)
        parity = all(_bags_bitwise_equal(out1[n], out2[n]) for n in out1)
        emit("storage_morsel_stream", t_stream,
             f"x{t_stream / max(t_oneshot, 1e-9):.2f}_vs_oneshot "
             f"morsels={mp.n_morsels} peak_rows={peak}/{full}",
             warm_ms=t_stream / 1e3)
        results.update(n_morsels=mp.n_morsels, parity=parity,
                       warm_retraces=warm, cold_traces=cold,
                       peak_rows=peak, full_rows=full)
        assert parity, "morsel-streamed output differs from one-shot"
        return results
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def run_compress_smoke() -> None:
    """The `make compress-smoke` CI gate (satellite of the compressed
    storage tentpole): compression ratio >= 2x on label columns,
    bit-for-bit decode parity with raw, >= 1 chunk skipped without
    paying a decode, and zero retraces across a >= 4-morsel streamed
    query."""
    comp = run_compression(n_orders=1200, fanout=40, chunk_rows=8192,
                           iters=3, smoke=True)
    assert comp["label_ratio"] >= 2.0, (
        f"compress smoke: label-column compression ratio "
        f"{comp['label_ratio']:.2f} < 2x")
    assert comp["decode_parity"], (
        "compress smoke: decoded columns differ from raw")

    # chunk skipping never pays a decode: zone maps are footer-only
    tmp = tempfile.mkdtemp(prefix="repro_storage_skipdec_")
    try:
        data = gen(200, 64)
        ds = StorageCatalog(tmp).write("tpch", data, INPUT_TYPES,
                                       chunk_rows=16)
        from repro.core import materialization as M
        from repro.serve.query_service import lift_program
        lifted, _ = lift_program(family(0.0))
        sp = M.shred_program(lifted, INPUT_TYPES,
                             domain_elimination=True)
        cp = CG.compile_program(sp, CATALOG)
        req = storage_requirements(cp, set(ds.parts))
        reset_storage_stats()
        ds.load_env(columns={p: r.columns for p, r in req.items()},
                    preds={p: r.pred for p, r in req.items()},
                    params={"__p0": 48.0})
        s = dict(STORAGE_STATS)
        assert s.get("chunks_skipped", 0) > 0, (
            "compress smoke: selective predicate skipped no chunks")
        assert s.get("chunks_decoded", 0) <= s.get("chunks_read", 0), (
            f"compress smoke: {s.get('chunks_decoded')} decodes for "
            f"{s.get('chunks_read')} chunk reads — a skipped chunk "
            f"paid a decode")
    finally:
        shutil.rmtree(tmp, ignore_errors=True)

    st = run_streamed(n_orders=200, n_parts=64, chunk_rows=16,
                      morsel_rows=50, smoke=True)
    assert st["n_morsels"] >= 4, (
        f"compress smoke: only {st['n_morsels']} morsels (want >= 4)")
    assert st["warm_retraces"] == 0, (
        f"compress smoke: {st['warm_retraces']} retraces across the "
        f"warm morsel stream")
    print(f"# compress smoke OK: label ratio x{comp['label_ratio']:.1f}"
          f" (total x{comp['compression_ratio']:.1f}), decode parity, "
          f"skip-without-decode, {st['n_morsels']} morsels / 0 warm "
          f"retraces")


def run(n_orders: int = 2000, n_parts: int = 512, chunk_rows: int = 64,
        smoke: bool = False) -> dict:
    tmp = tempfile.mkdtemp(prefix="repro_storage_bench_")
    results = {}
    try:
        data = gen(n_orders, n_parts)

        # -- generate vs cold load --------------------------------------
        t_gen = time_fn(lambda: CG.columnar_shred_inputs(
            data, INPUT_TYPES), warmup=0, iters=1 if smoke else 3)
        cat = StorageCatalog(tmp)
        t0 = time.perf_counter()
        ds = cat.write("tpch", data, INPUT_TYPES, chunk_rows=chunk_rows)
        write_ms = (time.perf_counter() - t0) * 1e3
        disk = ds.bytes_on_disk()
        emit("storage_generate", t_gen, f"n={n_orders}")

        def cold_load():
            return cat.open("tpch", refresh=True).load_env()

        reset_storage_stats()
        it_load = 1 if smoke else 3
        t_load = time_fn(cold_load, warmup=0, iters=it_load)
        ls = dict(STORAGE_STATS)
        emit("storage_cold_load", t_load,
             f"x{t_gen / max(t_load, 1e-9):.1f}_vs_generate "
             f"write_ms={write_ms:.1f}", bytes_on_disk=disk,
             bytes_read=ls.get("bytes_read", 0) // it_load,
             bytes_decoded=ls.get("bytes_decoded", 0) // it_load,
             decode_ms=_decode_ms(cold_load))
        results["load_vs_generate"] = t_gen / max(t_load, 1e-9)

        # -- pruned vs full scan ----------------------------------------
        from repro.serve.query_service import lift_program
        from repro.core import materialization as M
        lifted, _ = lift_program(family(0.0))
        sp = M.shred_program(lifted, INPUT_TYPES, domain_elimination=True)
        cp = CG.compile_program(sp, CATALOG)
        req = storage_requirements(cp, set(ds.parts))
        thresh = float(n_parts * 3 // 4)

        reset_storage_stats()
        t_full = time_fn(lambda: ds.load_env(), warmup=0,
                         iters=1 if smoke else 3)
        full_stats = {k: v // (1 if smoke else 3)
                      for k, v in STORAGE_STATS.items()}

        def pruned():
            return ds.load_env(
                columns={p: r.columns for p, r in req.items()},
                preds={p: r.pred for p, r in req.items()},
                params={"__p0": thresh})

        reset_storage_stats()
        t_pruned = time_fn(pruned, warmup=0, iters=1 if smoke else 3)
        pruned_stats = {k: v // (1 if smoke else 3)
                        for k, v in STORAGE_STATS.items()}
        emit("storage_full_scan", t_full,
             f"chunks={full_stats['chunks_read']}",
             chunks_skipped=0)
        emit("storage_pruned_scan", t_pruned,
             f"x{t_full / max(t_pruned, 1e-9):.1f}_vs_full "
             f"cols={pruned_stats['columns_read']}/"
             f"{pruned_stats['columns_read'] + pruned_stats['columns_pruned']}",
             chunks_skipped=pruned_stats["chunks_skipped"])
        results["pruned_vs_full"] = t_full / max(t_pruned, 1e-9)

        # -- zone-map skip rate under one warm service plan --------------
        svc = QueryService(INPUT_TYPES, catalog=CATALOG)
        CG.reset_trace_stats()
        svc.execute_stored(family(1.0), ds)     # cold: compile + trace
        cold_traces = CG.TRACE_STATS.get("traces", 0)
        skip_rates = {}
        for frac in (0.25, 0.5, 0.9):
            th = float(int(n_parts * frac))
            reset_storage_stats()
            svc.execute_stored(family(th), ds)
            s = dict(STORAGE_STATS)
            total = s["chunks_read"] + s["chunks_skipped"]
            rate = s["chunks_skipped"] / max(total, 1)
            skip_rates[frac] = rate
            # us_per_call stays a TIME field in the trajectory json; the
            # rate rides in its own key
            emit(f"storage_skip_rate_p{int(frac * 100)}",
                 0.0, f"threshold={th:.0f}",
                 chunks_skipped=s["chunks_skipped"],
                 skip_rate_pct=round(rate * 100, 1))
        warm_traces = CG.TRACE_STATS.get("traces", 0)
        results["skip_rates"] = skip_rates
        results["warm_retraces"] = warm_traces - cold_traces

        # -- smoke assertions (the `make ci` storage gate) ---------------
        if smoke:
            env = svc.shred_inputs(data)
            prog = family(float(n_parts // 2))
            rows_mem = svc.unshred(prog, env, svc.execute(prog, env), "Q")
            out_disk = svc.execute_stored(prog, ds)
            rows_disk = svc.unshred_stored(prog, ds, out_disk, "Q")
            assert _norm(rows_mem) == _norm(rows_disk), (
                "storage smoke: persisted-query result differs from "
                "in-memory result")
            assert max(skip_rates.values()) > 0, (
                "storage smoke: selective N.Param predicate skipped no "
                "chunks")
            assert results["warm_retraces"] == 0, (
                f"storage smoke: warm stored calls retraced "
                f"{results['warm_retraces']} times")
            print("# storage smoke OK: parity, >=1 chunk skipped, "
                  "0 warm retraces")
        return results
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small sizes + hard assertions (make ci)")
    ap.add_argument("--compress-smoke", action="store_true",
                    help="compressed-chunk + morsel-stream assertions "
                         "(make ci)")
    args = ap.parse_args()
    set_section("storage")
    if args.compress_smoke:
        run_compress_smoke()
    elif args.smoke:
        run(n_orders=200, n_parts=64, chunk_rows=16, smoke=True)
    else:
        run()
        run_compression()
        run_streamed()
    set_section(None)


if __name__ == "__main__":
    main()
