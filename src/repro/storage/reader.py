"""Reader for the shredded columnar storage format.

``StoredPart.load`` np-loads ONLY the requested columns and ONLY the
requested chunks, reassembling a ``FlatBag`` at a chosen capacity with
the persisted ``PhysicalProps`` (sort order / partitioning) re-attached
— chunks come back in written row order, so a persisted ``sorted_by``
still holds after skipping arbitrary chunks.

All load activity is metered in ``STORAGE_STATS`` (chunks read/skipped,
columns read/pruned, bytes read); the storage tests and
``benchmarks/storage.py`` assert pruning through these counters.
"""

from __future__ import annotations

import os
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.columnar.props import PhysicalProps
from repro.columnar.table import FlatBag, StringEncoder
from repro.core import nrc as N
from repro.errors import ChunkCorruptionError, MissingChunkError
from repro.faults import FAULTS

from . import encodings as E
from .format import (DatasetMeta, PartMeta, chunk_crc, chunk_may_match,
                     chunk_path, dir_bytes, read_footer)

from repro.obs.metrics import REGISTRY as _METRICS
from repro.obs.trace import span as _span

STORAGE_STATS = _METRICS.view("storage")
"""Host-side scan counters — live view onto the unified metrics
registry (``repro.obs``) under the ``storage.`` domain:
``chunks_read`` / ``chunks_skipped`` (zone maps), ``columns_read`` /
``columns_pruned`` (projection pushdown), ``parts_loaded``, and the
byte ledger — ``bytes_read`` is bytes that actually came off disk
(encoded chunks count their compressed blob, NOT the decoded rows),
``bytes_decoded`` / ``chunks_decoded`` meter the decode stage of
encoded chunks (its time is the ``decode`` span's)."""

DEVICE_DECODE = False
"""When True, encoded chunks decode through the Pallas kernels
(``kernels.ops.rle_expand`` / ``delta_unpack`` / ``bitunpack`` /
``dict_gather``) so decompression runs post-transfer on the
accelerator; the default NumPy path (``encodings.decode_chunk``) is
bit-for-bit identical — on this CPU container the kernels run in
interpret mode, so NumPy is the faster engine and the kernel path is
exercised by the parity tests."""


def reset_storage_stats() -> None:
    STORAGE_STATS.clear()


def _count(name: str, n: int = 1) -> None:
    _METRICS.inc("storage." + name, n)


def _to_device(host: np.ndarray, part: str, col: str) -> jax.Array:
    """One column's host-to-device copy, in a ``storage.to_device``
    span (``rows``, ``bytes``: what is handed to the device).
    ``device_put`` skips ``jnp.asarray``'s trace/convert layer — on the
    scan path this is a pure host->device copy."""
    with _span("storage.to_device", part=part, col=col,
               rows=host.shape[0], bytes=host.nbytes):
        return jax.device_put(host)


def _decode_device(enc: dict, m: Dict[str, np.ndarray]) -> np.ndarray:
    """Decode one encoded chunk's members (``encodings.unpack_members``)
    through the Pallas kernels. All kernels work on int64 bit-views
    (floats cross as raw bits), so the result is bit-for-bit
    ``encodings.decode_chunk``."""
    from repro.kernels import ops as K
    dtype = np.dtype(enc["dtype"])

    def to_i64(v: np.ndarray) -> np.ndarray:
        return v.view(np.int64) if v.dtype.kind == "f" \
            else v.astype(np.int64)

    def from_i64(out) -> np.ndarray:
        out = np.asarray(out)
        if dtype.kind == "f":
            return out.view(dtype)
        if dtype == np.bool_:
            return out != 0
        return out.astype(dtype, copy=False)

    c = enc["codec"]
    if c == "rle":
        lengths = m["lengths"].astype(np.int64)
        ends = np.cumsum(lengths)
        starts = ends - lengths
        n = int(ends[-1]) if ends.size else 0
        return from_i64(K.rle_expand(
            jnp.asarray(to_i64(m["values"])), jnp.asarray(starts),
            jnp.asarray(ends), n))
    if c == "delta":
        z = m["deltas"].astype(np.uint64)
        first = np.array([enc["first"]], np.uint64)
        return from_i64(K.delta_unpack(jnp.asarray(z),
                                       jnp.asarray(first)))
    if c == "bitpack":
        return from_i64(K.bitunpack(
            jnp.asarray(m["words"].astype(np.uint32)), int(enc["k"]),
            int(enc["vpw"]), int(enc["n"]), int(enc["lo"])))
    if c == "dict":
        return from_i64(K.dict_gather(
            jnp.asarray(to_i64(m["values"])),
            jnp.asarray(m["codes"].astype(np.int32))))
    raise ValueError(f"unknown codec {c!r}")


def restore_encoders(meta: DatasetMeta, strict: bool = True
                     ) -> Dict[str, StringEncoder]:
    """Rebuild the per-column string encoders exactly as persisted. The
    storage reader hands out STRICT encoders: decoding a code outside
    the persisted vocabulary raises instead of fabricating ``"<code>"``
    (a wrong code coming off disk is corruption, not a display issue)."""
    return {col: StringEncoder.from_vocab(rev, strict=strict)
            for col, rev in meta.encoders.items()}


@dataclass
class StoredPart:
    dirpath: str                # dataset directory
    meta: PartMeta

    @property
    def name(self) -> str:
        return self.meta.name

    @property
    def rows(self) -> int:
        return self.meta.rows

    @property
    def n_chunks(self) -> int:
        return len(self.meta.chunks)

    @property
    def columns(self) -> List[str]:
        return sorted(self.meta.schema)

    def bytes_on_disk(self) -> int:
        return dir_bytes(os.path.join(self.dirpath, self.meta.name))

    # -- planner statistics -------------------------------------------------
    def stats(self):
        """``skew.TableStats`` for this part: total rows, per-column
        distinct-count upper bounds from chunk zone maps, and the
        persisted streaming heavy-key sketch candidates. This is what
        the automatic skew pass (``plans.apply_skew_program``) and the
        cost estimator (``core.cost``) consume via ``table_stats``.

        Summing per-chunk distinct counts is sound but overcounts keys
        repeated across chunks badly (a foreign-key column with 400
        values looked like 2000+ distinct over many chunks, deflating
        every containment join estimate). For integer columns the zone
        maps carry exact ``lo``/``hi`` bounds, so the value-range width
        is a second sound upper bound; the minimum of the two (and the
        row count) is reported."""
        from repro.core.skew import HeavyKeySketch, TableStats
        distinct = {}
        lo: Dict[str, int] = {}
        hi: Dict[str, int] = {}
        ranged: Dict[str, bool] = {}
        for c in self.meta.chunks:
            for col, z in c.zones.items():
                distinct[col] = distinct.get(col, 0) + int(z["distinct"])
                zl, zh = z.get("lo"), z.get("hi")
                if (ranged.get(col, True) and isinstance(zl, int)
                        and isinstance(zh, int)):
                    ranged[col] = True
                    lo[col] = zl if col not in lo else min(lo[col], zl)
                    hi[col] = zh if col not in hi else max(hi[col], zh)
                elif zl is not None:
                    ranged[col] = False       # float column: no range bound
        for col, d in distinct.items():
            d = min(d, self.rows)
            if ranged.get(col) and col in lo:
                d = min(d, hi[col] - lo[col] + 1)
            distinct[col] = d
        heavy = {}
        for col, sj in self.meta.sketches.items():
            sk = HeavyKeySketch.from_json(sj)
            heavy[col] = [(v, cnt) for v, cnt in sk.counts.items()]
        return TableStats(rows=self.rows, distinct=distinct, heavy=heavy,
                          meters=dict(self.meta.meters))

    # -- zone-map chunk selection -----------------------------------------
    def select_chunks(self, pred: Optional[N.Expr],
                      params: Optional[dict] = None) -> List[int]:
        """Chunk indices that may contain rows satisfying ``pred``
        (all chunks when ``pred`` is None). Sound, not exact: a chunk is
        dropped only when its zone maps prove no row can match."""
        if pred is None:
            return list(range(self.n_chunks))
        return [i for i, c in enumerate(self.meta.chunks)
                if chunk_may_match(pred, c.zones, self.meta.schema, params)]

    # -- loading -----------------------------------------------------------
    def _load_chunk(self, col: str, i: int, verify: bool,
                    count: bool = True) -> np.ndarray:
        with _span("storage.chunk", part=self.meta.name, col=col,
                   chunk=i):
            return self._load_chunk_impl(col, i, verify, count)

    def _load_chunk_impl(self, col: str, i: int, verify: bool,
                         count: bool = True) -> np.ndarray:
        """np-load one chunk with the ``storage.chunk`` fault site,
        the codec decode stage, and integrity checks. A *torn* chunk
        (fewer rows — or a truncated encoded blob — on disk than the
        footer promises) is caught unconditionally by the row-count
        check (decoded rows derive from the payload, never the footer);
        silent *bit corruption* keeps the row count and is only caught
        by the CRC under ``verify=True`` — the CRC covers DECODED rows,
        so one checksum guards raw and encoded chunks alike.
        ``count=False`` keeps planner-internal peeks (morsel boundary
        reads) out of ``STORAGE_STATS``."""
        meta = self.meta
        path = chunk_path(self.dirpath, meta.name, col, i)
        enc = meta.chunks[i].encodings.get(col)
        rule = FAULTS.hit("storage.chunk", part=meta.name, col=col, chunk=i)
        if rule is not None and rule.kind == "missing":
            raise MissingChunkError(
                f"injected missing chunk: {meta.name}.{col} chunk {i}")
        try:
            a = np.load(path, mmap_mode="r")
            if count:
                _count("bytes_read", os.path.getsize(path))
        except FileNotFoundError as e:
            raise MissingChunkError(
                f"{meta.name}.{col} chunk {i}: {path} does not exist"
            ) from e
        except (OSError, ValueError) as e:
            raise ChunkCorruptionError(
                f"{meta.name}.{col} chunk {i}: unreadable npy "
                f"({e})") from e
        if rule is not None and rule.kind == "torn":
            # a torn WRITE: the on-disk payload (raw rows or encoded
            # blob) is shorter than the footer promises
            frac = float(rule.arg) if rule.arg is not None else 0.5
            a = np.asarray(a)[:int(a.shape[0] * frac)]
        if enc is not None:
            with _span("decode", part=meta.name, col=col, chunk=i,
                       codec=enc.get("codec")):
                # a blob that does not parse is a fault of the data; a
                # kernel that fails to compile or run is not, and raises
                # as itself
                try:
                    if DEVICE_DECODE:
                        members = E.unpack_members(enc, np.asarray(a))
                    else:
                        a = E.decode_chunk(enc, np.asarray(a))
                except ChunkCorruptionError:
                    raise
                except Exception as e:
                    raise ChunkCorruptionError(
                        f"{meta.name}.{col} chunk {i}: "
                        f"{enc.get('codec')} decode failed ({e!r})"
                    ) from e
                if DEVICE_DECODE:
                    a = _decode_device(enc, members)
                if count:
                    _count("bytes_decoded", int(a.nbytes))
                    _count("chunks_decoded")
        if rule is not None and rule.kind == "corrupt" and a.size:
            # silent bit rot observed by the consumer: flips a byte of
            # the DECODED rows, so the row count survives and only the
            # CRC (verify=True) can catch it — for raw and encoded
            # chunks alike
            a = np.array(a)         # writable copy of the mmap
            a.view(np.uint8).flat[0] ^= 0xFF
        if a.shape[0] != meta.chunks[i].rows:
            raise ChunkCorruptionError(
                f"{meta.name}.{col} chunk {i}: {a.shape[0]} rows on "
                f"disk != {meta.chunks[i].rows} in footer (torn write?)")
        if verify:
            want = meta.chunks[i].crcs.get(col)
            if want is not None and chunk_crc(np.asarray(a)) != want:
                raise ChunkCorruptionError(
                    f"{meta.name}.{col} chunk {i}: checksum mismatch")
        return a

    def load(self, columns: Optional[Sequence[str]] = None,
             chunks: Optional[Sequence[int]] = None,
             capacity: Optional[int] = None,
             verify: bool = False) -> FlatBag:
        """Read ``columns`` (default all) of ``chunks`` (default all)
        into a FlatBag of ``capacity`` (default: exactly the loaded
        rows; larger capacities pad with invalid rows so one compiled
        plan serves every chunk selection of the part). ``verify=True``
        checks each chunk against its footer CRC32 (chunks persisted
        before checksums existed are skipped)."""
        meta = self.meta
        if columns is None:
            cols = sorted(meta.schema)
        else:
            unknown = set(columns) - set(meta.schema)
            assert not unknown, (
                f"{meta.name}: unknown columns {sorted(unknown)}")
            cols = sorted(columns)
        sel = list(range(self.n_chunks)) if chunks is None \
            else sorted(chunks)
        with _span("storage.load_part", part=meta.name,
                   columns=tuple(cols), chunks=len(sel),
                   skipped=self.n_chunks - len(sel)):
            return self._load_selected(cols, sel, capacity, verify)

    def _load_selected(self, cols, sel, capacity, verify) -> FlatBag:
        meta = self.meta
        nrows = sum(meta.chunks[i].rows for i in sel)
        cap = capacity if capacity is not None else max(nrows, 1)
        assert cap >= nrows, (
            f"{meta.name}: capacity {cap} < selected rows {nrows}")
        _count("parts_loaded")
        _count("chunks_read", len(sel) * len(cols))
        _count("chunks_skipped", (self.n_chunks - len(sel)) * len(cols))
        _count("columns_read", len(cols))
        _count("columns_pruned", len(meta.schema) - len(cols))
        data = {}
        for col in cols:
            dtype = np.dtype(meta.dtypes[col])
            # empty + explicit tail-zero: loaded rows are overwritten
            # anyway, so a full-capacity memset would only add a
            # memory-bandwidth pass to every cold scan
            buf = np.empty(cap, dtype=dtype)
            off = 0
            for i in sel:
                a = self._load_chunk(col, i, verify)
                buf[off:off + a.shape[0]] = a
                off += a.shape[0]
            buf[off:] = dtype.type(0) if dtype.kind != "b" else False
            data[col] = _to_device(buf, meta.name, col)
        valid = _to_device(np.arange(cap) < nrows, meta.name, "valid")
        props = self._props(cols)
        return FlatBag(data, valid, props)

    def _props(self, cols: Sequence[str]) -> Optional[PhysicalProps]:
        """Persisted physical properties, restricted to loaded columns.
        ``sorted_by`` survives as its longest loaded prefix (chunk
        skipping preserves written row order); ``partitioning`` only
        when every column survives. Rows load valid-first, so
        ``invalid_last`` always holds."""
        meta = self.meta
        cs = set(cols)
        sb: Optional[tuple] = None
        if meta.sorted_by:
            pref = []
            for c in meta.sorted_by:
                if c not in cs:
                    break
                pref.append(c)
            sb = tuple(pref) or None
        part = meta.partitioning if (meta.partitioning
                                     and set(meta.partitioning) <= cs) \
            else None
        return PhysicalProps(sorted_by=sb, invalid_last=True,
                             partitioning=part)


def table_stats(dataset: "StoredDataset") -> Dict[str, object]:
    """{part name: skew.TableStats} over a whole dataset — the
    statistics bundle ``codegen.compile_program(skew_stats=...)`` and
    the query service feed to the automatic skew pass."""
    return {name: part.stats() for name, part in dataset.parts.items()}


class StoredDataset:
    """One opened dataset: parts, types, strict encoders."""

    def __init__(self, dirpath: str):
        self.dir = dirpath
        self.meta = read_footer(dirpath)
        self.parts: Dict[str, StoredPart] = {
            n: StoredPart(dirpath, pm) for n, pm in self.meta.parts.items()}
        self.input_types: Dict[str, N.BagT] = dict(self.meta.input_types)
        self.encoders: Dict[str, StringEncoder] = \
            restore_encoders(self.meta, strict=True)

    @property
    def name(self) -> str:
        return self.meta.name

    def part(self, name: str) -> StoredPart:
        return self.parts[name]

    def bytes_on_disk(self) -> int:
        return dir_bytes(self.dir)

    def fingerprint(self) -> tuple:
        """Cache-key component for the query service: identifies the
        dataset contents a compiled plan was bound against (schemas and
        row totals; chunk *selection* deliberately excluded — it varies
        per parameter binding under one warm plan)."""
        return (self.name, tuple(
            (n, p.rows, tuple(sorted(p.meta.schema.items())))
            for n, p in sorted(self.parts.items())))

    def load_env(self,
                 columns: Optional[Dict[str, Optional[set]]] = None,
                 preds: Optional[Dict[str, Optional[N.Expr]]] = None,
                 params: Optional[dict] = None,
                 capacities: Optional[Dict[str, int]] = None,
                 verify: bool = False
                 ) -> Dict[str, FlatBag]:
        """Materialize parts as an execution environment. ``columns``
        restricts parts AND their loaded columns (None value = all
        columns of that part); ``preds`` drives zone-map chunk skipping;
        ``capacities`` pins per-part capacities (the query service pins
        them to the full-part capacity class so chunk selection never
        changes traced shapes)."""
        names = sorted(columns) if columns is not None \
            else sorted(self.parts)
        env: Dict[str, FlatBag] = {}
        for name in names:
            part = self.parts[name]
            cols = None if columns is None else columns[name]
            pred = (preds or {}).get(name)
            sel = part.select_chunks(pred, params)
            cap = (capacities or {}).get(name)
            env[name] = part.load(
                columns=sorted(cols) if cols is not None else None,
                chunks=sel, capacity=cap, verify=verify)
        return env
