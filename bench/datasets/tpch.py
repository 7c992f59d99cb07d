"""TPC-H data for the benchmark, generated from a seed with NumPy in bulk
(nothing runs per row in Python).

Every table keeps TPC-H v3.0.1's full column set (Clause 1.4) with the
value domains of Clause 4.2.3, at a configuration's scale factor. A
configuration lists the tables it stores (``"tables"``); only those are
made. The engine holds strings as integer dictionary codes (its TPC-H
schema, ``repro.data.generators``, does the same), so a string column
is an ``int`` column here:

* a string from one of the specification's small domains (ship modes,
  priorities, brands, types, ...) is its index in that domain;
* a free-text or per-row string (comments, addresses, phones, clerks,
  customer names) is a code that stands for that row's text: as many
  distinct codes as the specification's generator would make distinct
  strings.

Money is held in whole cents and discounts and taxes in whole percent,
as float64: every value is a whole number, so sums of products stay
exact in float64 (and in the f32-pair float64 of a TPU up to about
2^48). Dates are days since 1970-01-01.

``part_key_skew`` > 0 draws ``l_partkey`` Zipf-distributed over ranks,
the paper's skew runs; ranks map to part keys through a permutation
drawn from the seed, so which parts are heavy (and so their prices) is
the seed's, not the price formula's.
"""

from __future__ import annotations

import numpy as np

from harness.seeds import rng_for

# TPC-H v3.0.1 Clause 4.2.3: STARTDATE 1992-01-01, CURRENTDATE 1995-06-17,
# ENDDATE 1998-12-31, in days since 1970-01-01
START_DATE = 8035
CURRENT_DATE = 9298
SHIP_MODES = 7          # REG AIR, AIR, RAIL, SHIP, TRUCK, MAIL, FOB
SHIP_INSTRUCTS = 4      # DELIVER IN PERSON, COLLECT COD, NONE, TAKE BACK RETURN
PRIORITIES = 5          # 1-URGENT .. 5-LOW
SEGMENTS = 5            # AUTOMOBILE, BUILDING, FURNITURE, MACHINERY, HOUSEHOLD
TYPES = 150             # 6 x 5 x 5 syllables
CONTAINERS = 40         # 5 x 8 syllables
NAME_WORDS = 92         # P_NAME: 5 distinct words of 92
FLAG_A, FLAG_N, FLAG_R = 0, 1, 2      # L_RETURNFLAG codes
STATUS_F, STATUS_O, STATUS_P = 0, 1, 2  # L_LINESTATUS / O_ORDERSTATUS
# Clause 4.2.3: N_REGIONKEY of the 25 nations, in N_NATIONKEY order
NATION_REGION = (0, 1, 1, 1, 4, 0, 3, 3, 2, 2, 4, 4, 2, 4, 0, 0, 0, 1, 2,
                 3, 4, 2, 3, 3, 1)
TEXT = (1 << 62)        # free-text codes are drawn from [0, TEXT)

# column -> engine type name, per table, in TPC-H's column order
SCHEMA = {
    "Lineitem": (("oid", "int"), ("pid", "int"), ("sid", "int"),
                 ("lnum", "int"), ("qty", "real"), ("eprice", "real"),
                 ("disc", "real"), ("tax", "real"), ("rflag", "int"),
                 ("lstatus", "int"), ("sdate", "int"), ("cdate", "int"),
                 ("rdate", "int"), ("sinstruct", "int"), ("smode", "int"),
                 ("lcomment", "int")),
    "Part": (("pid", "int"), ("pname", "int"), ("mfgr", "int"),
             ("brand", "int"), ("ptype", "int"), ("psize", "int"),
             ("container", "int"), ("price", "real"), ("pcomment", "int")),
    "Orders": (("oid", "int"), ("cid", "int"), ("ostatus", "int"),
               ("tprice", "real"), ("odate", "int"), ("opriority", "int"),
               ("clerk", "int"), ("spriority", "int"), ("ocomment", "int")),
    "Customer": (("cid", "int"), ("cname", "int"), ("address", "int"),
                 ("nid", "int"), ("phone", "int"), ("acctbal", "real"),
                 ("mktseg", "int"), ("ccomment", "int")),
    "Nation": (("nid", "int"), ("nname", "int"), ("rid", "int"),
               ("ncomment", "int")),
    "Region": (("rid", "int"), ("rname", "int"), ("rcomment", "int")),
}
# primary keys, declared to the planner as unique
UNIQUE_KEYS = {"Part__F": ("pid",), "Orders__F": ("oid",),
               "Customer__F": ("cid",), "Nation__F": ("nid",),
               "Region__F": ("rid",)}


def types(tables) -> dict:
    """The engine's bag type of each table."""
    from repro.core import nrc as N
    return {t: N.bag(N.tuple_t(**{c: N.SCALARS[k] for c, k in SCHEMA[t]}))
            for t in tables}


def retail_price_cents(pk: np.ndarray) -> np.ndarray:
    """P_RETAILPRICE of Clause 4.2.3, times 100:
    (90000 + ((pk / 10) mod 20001) + 100 * (pk mod 1000)) / 100 dollars."""
    return 90000 + (pk // 10) % 20001 + 100 * (pk % 1000)


def zipf_ranks(rng: np.random.Generator, n: int, skew: float,
               size: int) -> np.ndarray:
    """Ranks in [1, n], rank ``k`` drawn with probability proportional
    to ``k ** -skew``."""
    w = np.arange(1, n + 1, dtype=np.float64) ** -float(skew)
    cdf = np.cumsum(w)
    cdf /= cdf[-1]
    return np.minimum(np.searchsorted(cdf, rng.random(size),
                                      side="right") + 1, n)


def sizes(cfg: dict) -> dict:
    """Rows of each scaled table of a configuration (at least 1)."""
    sf = float(cfg["scale_factor"])
    return {k: max(1, int(round(cfg[f"{k}_per_sf"] * sf)))
            for k in ("orders", "parts", "customers", "suppliers",
                      "clerks")}


def order_keys(n: int) -> np.ndarray:
    """O_ORDERKEY of Clause 4.2.3: the first 8 of every 32 keys."""
    i = np.arange(n, dtype=np.int64)
    return (i // 8) * 32 + i % 8 + 1


def _text(rng, n: int) -> np.ndarray:
    return rng.integers(0, TEXT, size=n, dtype=np.int64)


def _part(rng, n: dict) -> dict:
    p = n["parts"]
    pk = np.arange(1, p + 1, dtype=np.int64)
    words = np.argsort(rng.random((p, NAME_WORDS)), axis=1)[:, :5]
    mfgr = rng.integers(1, 6, size=p)
    return {"pid": pk,
            "pname": (words * NAME_WORDS ** np.arange(5)).sum(axis=1),
            "mfgr": mfgr,
            "brand": mfgr * 10 + rng.integers(1, 6, size=p),
            "ptype": rng.integers(0, TYPES, size=p),
            "psize": rng.integers(1, 51, size=p),
            "container": rng.integers(0, CONTAINERS, size=p),
            "price": retail_price_cents(pk).astype(np.float64),
            "pcomment": _text(rng, p)}


def _orders_lines(rng, cfg: dict, n: dict) -> tuple:
    """(Orders, Lineitem) columns: each order's lines, and the order
    columns that Clause 4.2.3 derives from them."""
    o = n["orders"]
    lo, hi = cfg["lines_per_order"]
    per_order = rng.integers(lo, hi + 1, size=o)
    n_lines = int(per_order.sum())
    okey = order_keys(o)
    first = np.cumsum(per_order) - per_order
    line_order = np.repeat(np.arange(o), per_order)
    odate = START_DATE + rng.integers(0, cfg["order_days"], size=o)
    p = n["parts"]
    if float(cfg["part_key_skew"]) > 0:
        ranks = zipf_ranks(rng, p, cfg["part_key_skew"], n_lines)
        pid = rng.permutation(np.arange(1, p + 1, dtype=np.int64))[ranks - 1]
    else:
        pid = rng.integers(1, p + 1, size=n_lines)
    s = n["suppliers"]
    supp_i = rng.integers(0, 4, size=n_lines)
    sid = (pid + supp_i * (s // 4 + (pid - 1) // s)) % s + 1
    q_lo, q_hi = cfg["quantity"]
    qty = rng.integers(q_lo, q_hi + 1, size=n_lines).astype(np.float64)
    eprice = qty * retail_price_cents(pid)
    disc = rng.integers(0, 11, size=n_lines).astype(np.float64)
    tax = rng.integers(0, 9, size=n_lines).astype(np.float64)
    l_odate = odate[line_order]
    sdate = l_odate + rng.integers(1, 122, size=n_lines)
    cdate = l_odate + rng.integers(30, 91, size=n_lines)
    rdate = sdate + rng.integers(1, 31, size=n_lines)
    rflag = np.where(rdate <= CURRENT_DATE,
                     np.where(rng.random(n_lines) < 0.5, FLAG_R, FLAG_A),
                     FLAG_N)
    lstatus = np.where(sdate > CURRENT_DATE, STATUS_O, STATUS_F)
    lines = {"oid": okey[line_order], "pid": pid, "sid": sid,
             "lnum": np.arange(n_lines) - first[line_order] + 1,
             "qty": qty, "eprice": eprice, "disc": disc, "tax": tax,
             "rflag": rflag, "lstatus": lstatus, "sdate": sdate,
             "cdate": cdate, "rdate": rdate,
             "sinstruct": rng.integers(0, SHIP_INSTRUCTS, size=n_lines),
             "smode": rng.integers(0, SHIP_MODES, size=n_lines),
             "lcomment": _text(rng, n_lines)}
    # O_TOTALPRICE = sum(L_EXTENDEDPRICE * (1 + L_TAX) * (1 - L_DISCOUNT)),
    # rounded to whole cents
    net = eprice * (100 + tax) * (100 - disc) / 1e4
    tprice = np.round(np.bincount(line_order, weights=net, minlength=o))
    n_open = np.bincount(line_order, weights=lstatus == STATUS_O,
                         minlength=o)
    ostatus = np.where(n_open == 0, STATUS_F,
                       np.where(n_open == per_order, STATUS_O, STATUS_P))
    # O_CUSTKEY: never a multiple of 3 (a third of customers order nothing)
    k = rng.integers(0, (2 * n["customers"]) // 3, size=o)
    orders = {"oid": okey, "cid": 3 * (k // 2) + 1 + (k % 2),
              "ostatus": ostatus, "tprice": tprice, "odate": odate,
              "opriority": rng.integers(0, PRIORITIES, size=o),
              "clerk": rng.integers(1, n["clerks"] + 1, size=o),
              "spriority": np.zeros(o, dtype=np.int64),
              "ocomment": _text(rng, o)}
    return orders, lines


def _customer(rng, n: dict) -> dict:
    c = n["customers"]
    ck = np.arange(1, c + 1, dtype=np.int64)
    nid = rng.integers(0, len(NATION_REGION), size=c)
    return {"cid": ck, "cname": ck.copy(), "address": _text(rng, c),
            "nid": nid,
            # C_PHONE: country code (nation + 10), then 10 random digits
            "phone": (nid + 10) * 10**10 + rng.integers(0, 10**10, size=c),
            "acctbal": rng.integers(-99999, 1000000, size=c)
            .astype(np.float64),
            "mktseg": rng.integers(0, SEGMENTS, size=c),
            "ccomment": _text(rng, c)}


def _nation(rng) -> dict:
    nk = np.arange(len(NATION_REGION), dtype=np.int64)
    return {"nid": nk, "nname": nk.copy(),
            "rid": np.asarray(NATION_REGION, dtype=np.int64),
            "ncomment": _text(rng, nk.size)}


def _region(rng) -> dict:
    rk = np.arange(5, dtype=np.int64)
    return {"rid": rk, "rname": rk.copy(), "rcomment": _text(rng, 5)}


def generate(cfg: dict, seed: int) -> dict:
    """Column arrays of the configuration's tables, keyed
    ``<table>.<column>``, in the engine's dtypes."""
    tables = list(cfg["tables"])
    n = sizes(cfg)
    made = {}
    if {"Orders", "Lineitem"} & set(tables):
        made["Orders"], made["Lineitem"] = _orders_lines(
            rng_for(seed, 0), cfg, n)
    if "Part" in tables:
        made["Part"] = _part(rng_for(seed, 3), n)
    if "Customer" in tables:
        made["Customer"] = _customer(rng_for(seed, 4), n)
    if "Nation" in tables:
        made["Nation"] = _nation(rng_for(seed, 5))
    if "Region" in tables:
        made["Region"] = _region(rng_for(seed, 6))
    dtype = {"int": np.int64, "real": np.float64}
    return {f"{t}.{c}": np.asarray(made[t][c], dtype=dtype[k])
            for t in tables for c, k in SCHEMA[t]}
