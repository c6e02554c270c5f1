"""TPC-H tables from a seed, as Arrow tables: the benchmark's own generator.

It follows the specification's clause 4.2.3 column by column, in bulk numpy
(no Python loop over rows), and imports nothing of ``spark_rapids_tpu``:

* ORDERS: 10 an existing customer (1,500,000 x SF), sparse keys (the first
  8 of every 32), ``o_custkey`` never a multiple of 3, ``o_orderdate``
  uniform in [1992-01-01, 1998-12-31 - 151 days], ``o_totalprice`` and
  ``o_orderstatus`` worked out from the order's lines.
* LINEITEM: 1 to 7 lines an order (so about, not exactly, 6,000,000 x SF
  rows: the count is the seed's), in order-key order with ``l_linenumber``
  1..n, ``l_suppkey`` by the part-supplier formula, ``l_extendedprice`` =
  quantity x the part's retail price, ship/commit/receipt dates from the
  order's date, ``l_returnflag`` and ``l_linestatus`` from the dates
  against CURRENTDATE 1995-06-17 (4 skewed Q1 groups).
* PART's retail price by formula, PARTSUPP's 4 suppliers a part by
  formula, NATION and REGION fixed, phones by clause 4.2.2.9, text columns
  with the specification's length ranges (``l_comment`` 10..43,
  ``o_comment`` 19..78, ``c_comment`` 29..116, ``ps_comment`` 49..198, ...)
  cut from a pool of sentences made by clause 4.2.2.10's grammar and word
  lists, addresses 10..40 random characters.

Where it is not dbgen (``assumed`` in the configuration files): numpy's
PCG64 streams instead of dbgen's, so the rows are the specification's in
distribution and not dbgen's byte for byte; money is float64 in whole
cents, not ``decimal(15,2)``; the text pool is 2 MiB, not 300 MB, and a
column's comments are consecutive cuts of it from a random start.

Each table has a random stream of its own (``default_rng([seed, stream])``),
so a cell generates only the tables its queries name; orders and lineitem
share the stream of the order lines, which both are worked out from.
"""

import datetime

import numpy as np
import pyarrow as pa

SEGMENTS = ["AUTOMOBILE", "BUILDING", "FURNITURE", "MACHINERY", "HOUSEHOLD"]
PRIORITIES = ["1-URGENT", "2-HIGH", "3-MEDIUM", "4-NOT SPECIFIED", "5-LOW"]
SHIPMODES = ["REG AIR", "AIR", "RAIL", "SHIP", "TRUCK", "MAIL", "FOB"]
SHIPINSTRUCT = ["DELIVER IN PERSON", "COLLECT COD", "NONE",
                "TAKE BACK RETURN"]
REGIONS = ["AFRICA", "AMERICA", "ASIA", "EUROPE", "MIDDLE EAST"]
# clause 4.2.3: nation key, name, region key
NATIONS = [("ALGERIA", 0), ("ARGENTINA", 1), ("BRAZIL", 1), ("CANADA", 1),
           ("EGYPT", 4), ("ETHIOPIA", 0), ("FRANCE", 3), ("GERMANY", 3),
           ("INDIA", 2), ("INDONESIA", 2), ("IRAN", 4), ("IRAQ", 4),
           ("JAPAN", 2), ("JORDAN", 4), ("KENYA", 0), ("MOROCCO", 0),
           ("MOZAMBIQUE", 0), ("PERU", 1), ("CHINA", 2), ("ROMANIA", 3),
           ("SAUDI ARABIA", 4), ("VIETNAM", 2), ("RUSSIA", 3),
           ("UNITED KINGDOM", 3), ("UNITED STATES", 1)]
TYPES = [f"{a} {b} {c}"
         for a in ("STANDARD", "SMALL", "MEDIUM", "LARGE", "ECONOMY", "PROMO")
         for b in ("ANODIZED", "BURNISHED", "PLATED", "POLISHED", "BRUSHED")
         for c in ("TIN", "NICKEL", "BRASS", "STEEL", "COPPER")]
CONTAINERS = [f"{a} {b}"
              for a in ("SM", "LG", "MED", "JUMBO", "WRAP")
              for b in ("CASE", "BOX", "BAG", "JAR", "PKG", "PACK", "CAN",
                        "DRUM")]
COLORS = """almond antique aquamarine azure beige bisque black blanched blue
blush brown burlywood burnished chartreuse chiffon chocolate coral cornflower
cornsilk cream cyan dark deep dim dodger drab firebrick floral forest frosted
gainsboro ghost goldenrod green grey honeydew hot indian ivory khaki lace
lavender lawn lemon light lime linen magenta maroon medium metallic midnight
mint misty moccasin navajo navy olive orange orchid pale papaya peach peru
pink plum powder puff purple red rose rosy royal saddle salmon sandy seashell
sienna sky slate smoke snow spring steel tan thistle tomato turquoise violet
wheat white yellow""".split()

# clause 4.2.2.10: the word lists of the text grammar
NOUNS = """foxes ideas theodolites pinto_beans instructions dependencies
excuses platelets asymptotes courts dolphins multipliers sauternes warthogs
frets dinos attainments somas Tiresias' patterns forges braids hockey_players
frays warhorses dugouts notornis epitaphs pearls tithes waters orbits gifts
sheaves depths sentiments decoys realms pains grouches escapades""".split()
VERBS = """sleep wake are cajole haggle nag use boost affix detect integrate
maintain nod was lose sublate solve thrash promise engage hinder print x-ray
breach eat grow impress mold poach serve run dazzle snooze doze unwind kindle
play hang believe doubt""".split()
ADJECTIVES = """furious sly careful blithe quick fluffy slow quiet ruthless
thin close dogged daring brave stealthy permanent enticing idle busy regular
final ironic even bold silent special pending unusual express""".split()
ADVERBS = """sometimes always never furiously slyly carefully blithely quickly
fluffily slowly quietly ruthlessly thinly closely doggedly daringly bravely
stealthily permanently enticingly idly busily regularly finally ironically
evenly boldly silently""".split()
PREPOSITIONS = """about above according_to across after against along
alongside_of among around at atop before behind beneath beside besides between
beyond by despite during except for from in_place_of inside instead_of into
near of on outside over past since through throughout to toward under until up
upon without with within""".split()
AUXILIARIES = """do may might shall will would can could should ought_to must
will_have_to shall_have_to could_have_to should_have_to must_have_to need_to
try_to""".split()
TERMINATORS = [".", ";", ":", "?", "!", "--"]

# rows at scale factor 1 (clause 4.2.5); nation and region do not scale.
# LINEITEM is not here: 1 to 7 lines an order, so its count is the seed's
# (4 x orders on average; dbgen's own SF1 draw gives 6,001,215)
ROWS_SF1 = {"orders": 1_500_000, "customer": 150_000, "part": 200_000,
            "supplier": 10_000, "partsupp": 800_000, "nation": 25,
            "region": 5}
_MIN_ROWS = {"orders": 100, "customer": 50, "part": 40, "supplier": 10,
             "partsupp": 160}
# one random stream per table; ``order_lines`` is shared by orders and
# lineitem
_STREAMS = {"order_lines": 0, "orders": 1, "lineitem": 2, "customer": 3,
            "part": 4, "supplier": 5, "partsupp": 6, "nation": 7,
            "region": 8}


def _days(year, month, day):
    return (datetime.date(year, month, day) - datetime.date(1970, 1, 1)).days


STARTDATE = _days(1992, 1, 1)
ENDDATE = _days(1998, 12, 31)
CURRENTDATE = _days(1995, 6, 17)

# table -> columns in the specification's order (clause 1.4)
COLUMNS = {
    "lineitem": ["l_orderkey", "l_partkey", "l_suppkey", "l_linenumber",
                 "l_quantity", "l_extendedprice", "l_discount", "l_tax",
                 "l_returnflag", "l_linestatus", "l_shipdate",
                 "l_commitdate", "l_receiptdate", "l_shipinstruct",
                 "l_shipmode", "l_comment"],
    "orders": ["o_orderkey", "o_custkey", "o_orderstatus", "o_totalprice",
               "o_orderdate", "o_orderpriority", "o_clerk",
               "o_shippriority", "o_comment"],
    "customer": ["c_custkey", "c_name", "c_address", "c_nationkey",
                 "c_phone", "c_acctbal", "c_mktsegment", "c_comment"],
    "part": ["p_partkey", "p_name", "p_mfgr", "p_brand", "p_type", "p_size",
             "p_container", "p_retailprice", "p_comment"],
    "supplier": ["s_suppkey", "s_name", "s_address", "s_nationkey",
                 "s_phone", "s_acctbal", "s_comment"],
    "partsupp": ["ps_partkey", "ps_suppkey", "ps_availqty", "ps_supplycost",
                 "ps_comment"],
    "nation": ["n_nationkey", "n_name", "n_regionkey", "n_comment"],
    "region": ["r_regionkey", "r_name", "r_comment"],
}


def rows(table, sf):
    """Rows of ``table`` at scale factor ``sf`` (floors keep tiny
    rehearsal scales non-empty).  Not for lineitem: see ``ROWS_SF1``."""
    if table in ("nation", "region"):
        return ROWS_SF1[table]
    return max(int(ROWS_SF1[table] * sf), _MIN_ROWS[table])


def _rng(seed, stream):
    return np.random.default_rng([int(seed), _STREAMS[stream]])


def _pick(rng, values, n):
    """``n`` uniform draws from ``values`` as a plain Arrow string column."""
    return _coded(rng.integers(0, len(values), n, dtype=np.int32), values)


def _coded(codes, values):
    return pa.DictionaryArray.from_arrays(
        pa.array(np.asarray(codes, dtype=np.int32)),
        pa.array(values, pa.string())).cast(pa.string())


_POOLS = {}


def _text_pool():
    """About 2 MiB of sentences by the grammar of clause 4.2.2.10, the same
    for every seed (dbgen's pool is fixed too)."""
    if "text" in _POOLS:
        return _POOLS["text"]
    rng, n = np.random.default_rng(4_2_2_10), 40_000

    def words(values, size=n):
        clean = np.array([v.replace("_", " ") for v in values])
        return clean[rng.integers(0, len(clean), size)]

    def join(*parts):
        out = parts[0]
        for p in parts[1:]:
            out = np.char.add(out, p)
        return out

    def noun_phrase():
        form = rng.integers(0, 4, n)
        noun, adj, adj2, adv = (words(NOUNS), words(ADJECTIVES),
                                words(ADJECTIVES), words(ADVERBS))
        return np.select(
            [form == 0, form == 1, form == 2],
            [noun, join(adj, " ", noun), join(adj, ", ", adj2, " ", noun)],
            join(adv, " ", adj, " ", noun))

    def verb_phrase():
        form = rng.integers(0, 4, n)
        verb, aux, adv = words(VERBS), words(AUXILIARIES), words(ADVERBS)
        return np.select(
            [form == 0, form == 1, form == 2],
            [verb, join(aux, " ", verb), join(verb, " ", adv)],
            join(aux, " ", verb, " ", adv))

    def prep_phrase():
        return join(words(PREPOSITIONS), " the ", noun_phrase())

    form = rng.integers(0, 5, n)
    np1, vp, term = noun_phrase(), verb_phrase(), words(TERMINATORS)
    sentences = np.select(
        [form == 0, form == 1, form == 2, form == 3],
        [join(np1, " ", vp, term),
         join(np1, " ", vp, " ", prep_phrase(), term),
         join(np1, " ", vp, " ", noun_phrase(), term),
         join(np1, " ", prep_phrase(), " ", vp, term)],
        join(np1, " ", prep_phrase(), " ", vp, " ", prep_phrase(), term))
    pool = np.frombuffer(" ".join(sentences.tolist()).encode("ascii"),
                         dtype=np.uint8)
    _POOLS["text"] = pool
    return pool


def _char_pool():
    """1 MiB of random characters for the v-strings of clause 4.2.2.7
    (an alphabet of 64)."""
    if "chars" not in _POOLS:
        alphabet = np.frombuffer(
            (b"0123456789abcdefghijklmnopqrstuvwxyz"
             b"ABCDEFGHIJKLMNOPQRSTUVWXYZ, "), dtype=np.uint8)
        _POOLS["chars"] = alphabet[np.random.default_rng(4_2_2_7).integers(
            0, len(alphabet), 1 << 20)]
    return _POOLS["chars"]


def _cuts(rng, n, lo, hi, pool):
    """``n`` strings of ``lo``..``hi`` bytes (uniform): consecutive cuts
    of ``pool`` from a random start, so the Arrow data buffer is the pool
    tiled and nothing is gathered row by row."""
    chunks, at, start = [], 0, int(rng.integers(0, len(pool)))
    per_chunk = max(((1 << 31) - 1) // hi, 1)
    while at < n or not chunks:
        m = min(n - at, per_chunk)
        lengths = rng.integers(lo, hi + 1, m)
        offsets = np.zeros(m + 1, dtype=np.int32)
        np.cumsum(lengths, out=offsets[1:])
        data = np.resize(np.roll(pool, -start), int(offsets[-1]))
        start = (start + int(offsets[-1])) % len(pool)
        chunks.append(pa.StringArray.from_buffers(
            m, pa.py_buffer(offsets), pa.py_buffer(data)))
        at += m
    return chunks[0] if len(chunks) == 1 else pa.chunked_array(chunks)


def _text(rng, n, lo, hi):
    return _cuts(rng, n, lo, hi, _text_pool())


def _vstring(rng, n, lo=10, hi=40):
    return _cuts(rng, n, lo, hi, _char_pool())


def _dates(days):
    return pa.array(np.asarray(days, dtype=np.int32)).view(pa.date32())


def _money(rng, lo, hi, n):
    """Uniform in [lo, hi] in whole cents."""
    return rng.integers(round(lo * 100), round(hi * 100) + 1, n) / 100.0


def _numbered(prefix, keys, width=9):
    digits = np.char.zfill(np.asarray(keys).astype(str), width)
    return pa.array(np.char.add(prefix, digits), pa.string())


def _phones(rng, nation):
    """Clause 4.2.2.9: country code nation + 10, then 3-3-4 digits."""
    n = len(nation)
    parts = [(nation + 10).astype(str)] + [
        rng.integers(lo, hi, n).astype(str)
        for lo, hi in ((100, 1000), (100, 1000), (1000, 10000))]
    out = parts[0]
    for p in parts[1:]:
        out = np.char.add(np.char.add(out, "-"), p)
    return pa.array(out, pa.string())


def retail_cents(partkey):
    """P_RETAILPRICE in cents, by the specification's formula."""
    return 90000 + (partkey // 10) % 20001 + 100 * (partkey % 1000)


def _part_supplier(partkey, i, n_supp):
    """The ``i``-th (0..3) supplier of a part, by the specification's
    formula (PS_SUPPKEY and L_SUPPKEY)."""
    return (partkey + i * (n_supp // 4 + (partkey - 1) // n_supp)) \
        % n_supp + 1


_ORDER_LINES = {}


def _order_lines(sf, seed):
    """What orders and lineitem are both worked out from: every order's
    date and number of lines, and every line's numbers.  Kept for the
    last (sf, seed) only."""
    key = (sf, seed)
    if key in _ORDER_LINES:
        return _ORDER_LINES[key]
    rng, n_orders = _rng(seed, "order_lines"), rows("orders", sf)
    odate = STARTDATE + rng.integers(0, ENDDATE - 151 - STARTDATE + 1,
                                     n_orders)
    nlines = rng.integers(1, 8, n_orders)
    n = int(nlines.sum())
    order = np.repeat(np.arange(n_orders), nlines)
    first = np.cumsum(nlines) - nlines
    index = np.arange(n_orders, dtype=np.int64)
    partkey = rng.integers(1, rows("part", sf) + 1, n)
    quantity = rng.integers(1, 51, n)
    shipdate = odate[order] + rng.integers(1, 122, n)
    lines = {
        "n_orders": n_orders, "nlines": nlines, "order": order,
        "orderdate": odate,
        # only the first 8 of every 32 keys are used
        "orderkey": (index // 8) * 32 + index % 8 + 1,
        "linenumber": (np.arange(n) - first[order] + 1).astype(np.int32),
        "partkey": partkey,
        "suppkey": _part_supplier(partkey, rng.integers(0, 4, n),
                                  rows("supplier", sf)),
        "quantity": quantity,
        "price_cents": quantity * retail_cents(partkey),
        "discount": rng.integers(0, 11, n),
        "tax": rng.integers(0, 9, n),
        "shipdate": shipdate,
        "commitdate": odate[order] + rng.integers(30, 91, n),
        "receiptdate": shipdate + rng.integers(1, 31, n),
    }
    _ORDER_LINES.clear()
    _ORDER_LINES[key] = lines
    return lines


def _orders(sf, seed):
    rng, x = _rng(seed, "orders"), _order_lines(sf, seed)
    n = x["n_orders"]
    custkeys = np.arange(1, rows("customer", sf) + 1, dtype=np.int64)
    custkeys = custkeys[custkeys % 3 != 0]
    charge = (x["price_cents"] / 100.0) * (1 + x["tax"] / 100.0) \
        * (1 - x["discount"] / 100.0)
    still_open = np.bincount(x["order"], x["shipdate"] > CURRENTDATE, n)
    status = np.where(still_open == 0, 1,
                      np.where(still_open == x["nlines"], 0, 2))
    return pa.table({
        "o_orderkey": x["orderkey"],
        "o_custkey": rng.choice(custkeys, n),
        "o_orderstatus": _coded(status, ["O", "F", "P"]),
        "o_totalprice": np.bincount(x["order"], charge, n).round(2),
        "o_orderdate": _dates(x["orderdate"]),
        "o_orderpriority": _pick(rng, PRIORITIES, n),
        "o_clerk": _pick(rng, [f"Clerk#{k:09d}" for k in range(
            1, max(int(1000 * sf), 1) + 1)], n),
        "o_shippriority": np.zeros(n, dtype=np.int32),
        "o_comment": _text(rng, n, 19, 78),
    })


def _lineitem(sf, seed):
    rng, x = _rng(seed, "lineitem"), _order_lines(sf, seed)
    n = len(x["order"])
    received = x["receiptdate"] <= CURRENTDATE
    flag = np.where(received, rng.integers(0, 2, n), 2)
    return pa.table({
        "l_orderkey": x["orderkey"][x["order"]],
        "l_partkey": x["partkey"],
        "l_suppkey": x["suppkey"],
        "l_linenumber": x["linenumber"],
        "l_quantity": x["quantity"].astype(np.float64),
        "l_extendedprice": x["price_cents"] / 100.0,
        "l_discount": x["discount"] / 100.0,
        "l_tax": x["tax"] / 100.0,
        "l_returnflag": _coded(flag, ["R", "A", "N"]),
        "l_linestatus": _coded(x["shipdate"] > CURRENTDATE, ["F", "O"]),
        "l_shipdate": _dates(x["shipdate"]),
        "l_commitdate": _dates(x["commitdate"]),
        "l_receiptdate": _dates(x["receiptdate"]),
        "l_shipinstruct": _pick(rng, SHIPINSTRUCT, n),
        "l_shipmode": _pick(rng, SHIPMODES, n),
        "l_comment": _text(rng, n, 10, 43),
    })


def _customer(sf, seed):
    rng, n = _rng(seed, "customer"), rows("customer", sf)
    keys = np.arange(1, n + 1, dtype=np.int64)
    nation = rng.integers(0, 25, n).astype(np.int64)
    return pa.table({
        "c_custkey": keys,
        "c_name": _numbered("Customer#", keys),
        "c_address": _vstring(rng, n),
        "c_nationkey": nation,
        "c_phone": _phones(rng, nation),
        "c_acctbal": _money(rng, -999.99, 9999.99, n),
        "c_mktsegment": _pick(rng, SEGMENTS, n),
        "c_comment": _text(rng, n, 29, 116),
    })


def _part(sf, seed):
    rng, n = _rng(seed, "part"), rows("part", sf)
    keys = np.arange(1, n + 1, dtype=np.int64)
    # five different colours a name
    five = np.argpartition(rng.random((n, len(COLORS))), 5, axis=1)[:, :5]
    colors = np.array(COLORS)
    name = colors[five[:, 0]]
    for k in range(1, 5):
        name = np.char.add(np.char.add(name, " "), colors[five[:, k]])
    mfgr = rng.integers(1, 6, n)
    brand = mfgr * 10 + rng.integers(1, 6, n)
    return pa.table({
        "p_partkey": keys,
        "p_name": pa.array(name, pa.string()),
        "p_mfgr": pa.array(np.char.add("Manufacturer#", mfgr.astype(str)),
                           pa.string()),
        "p_brand": pa.array(np.char.add("Brand#", brand.astype(str)),
                            pa.string()),
        "p_type": _pick(rng, TYPES, n),
        "p_size": rng.integers(1, 51, n).astype(np.int32),
        "p_container": _pick(rng, CONTAINERS, n),
        "p_retailprice": retail_cents(keys) / 100.0,
        "p_comment": _text(rng, n, 5, 22),
    })


def _supplier(sf, seed):
    rng, n = _rng(seed, "supplier"), rows("supplier", sf)
    keys = np.arange(1, n + 1, dtype=np.int64)
    nation = rng.integers(0, 25, n).astype(np.int64)
    # 5 x SF suppliers' comments hold "Customer ... Complaints" and as
    # many "Customer ... Recommends" (Q16 looks for the first)
    comments = _text(rng, n, 25, 100).to_pylist()
    marked = rng.choice(n, min(2 * max(int(5 * sf), 1), n), replace=False)
    for k, row in enumerate(marked):
        word = "Complaints" if k % 2 == 0 else "Recommends"
        text = comments[row]
        comments[row] = ("Customer " + text[9:max(len(text) - 11, 9)]
                         + " " + word)
    return pa.table({
        "s_suppkey": keys,
        "s_name": _numbered("Supplier#", keys),
        "s_address": _vstring(rng, n),
        "s_nationkey": nation,
        "s_phone": _phones(rng, nation),
        "s_acctbal": _money(rng, -999.99, 9999.99, n),
        "s_comment": pa.array(comments, pa.string()),
    })


def _partsupp(sf, seed):
    rng = _rng(seed, "partsupp")
    n_part, n_supp = rows("part", sf), rows("supplier", sf)
    part = np.repeat(np.arange(1, n_part + 1, dtype=np.int64), 4)
    i = np.tile(np.arange(4, dtype=np.int64), n_part)
    return pa.table({
        "ps_partkey": part,
        "ps_suppkey": _part_supplier(part, i, n_supp),
        "ps_availqty": rng.integers(1, 10000, len(part)).astype(np.int32),
        "ps_supplycost": _money(rng, 1, 1000, len(part)),
        "ps_comment": _text(rng, len(part), 49, 198),
    })


def _nation(sf, seed):
    return pa.table({
        "n_nationkey": np.arange(25, dtype=np.int64),
        "n_name": pa.array([name for name, _ in NATIONS], pa.string()),
        "n_regionkey": np.array([r for _, r in NATIONS], dtype=np.int64),
        "n_comment": _text(_rng(seed, "nation"), 25, 31, 114)})


def _region(sf, seed):
    return pa.table({
        "r_regionkey": np.arange(5, dtype=np.int64),
        "r_name": pa.array(REGIONS, pa.string()),
        "r_comment": _text(_rng(seed, "region"), 5, 31, 115)})


_TABLES = {"lineitem": _lineitem, "orders": _orders, "customer": _customer,
           "part": _part, "supplier": _supplier, "partsupp": _partsupp,
           "nation": _nation, "region": _region}


def gen_table(name, sf, seed):
    """One table as an Arrow table; the same (name, sf, seed) gives the
    same rows whatever else is generated."""
    return _TABLES[name](float(sf), int(seed))


def gen_tables(names, sf, seed):
    tables = {name: gen_table(name, sf, seed) for name in names}
    _ORDER_LINES.clear()
    return tables
