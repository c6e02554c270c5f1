"""TPC-DS store-channel tables from a seed, as Arrow tables, at the
specification's types: the benchmark's own generator.

Five tables with every column of the specification's clause 2 in its
order, in bulk numpy (no Python loop over rows), importing nothing of
``spark_rapids_tpu``:

* STORE_SALES (23 columns, 2,880,404 x SF rows): int32 surrogate keys,
  ``ss_quantity`` int32, twelve ``decimal(7,2)`` money columns worked out
  in whole cents by the pricing rules (list = wholesale x (1 + markup),
  sales = list x (1 - discount), extended = x quantity, a coupon on one
  line in five, tax 0..9%).  Rows come in ticket order: 8 to 16 lines a
  ticket, one date / time / customer / demographics / household /
  address / store a ticket, tickets in date order, dates by the
  calendar's seasonal weights over 1998-01-02 .. 2002-12-31.  A line's
  item is the revision, valid on the ticket's date, of one of the
  ``i_item_id`` business keys: consecutive entries of a permutation from a
  random start, so no item comes twice on a ticket.  Every nullable
  column is NULL in about 4.5% of the rows, each on its own;
  ``ss_item_sk`` and ``ss_ticket_number`` (the primary key) never.
* DATE_DIM (28 columns, 73,049 rows: 1900-01-02 .. 2100-01-01, the same at
  every scale), ``d_date`` date32.
* ITEM (22 columns, 18,000 x SF rows) as a type-2 history: business keys
  take 1, 2, 3, 1, 2, 3 ... revisions, so 6 rows hold 3 ``i_item_id``
  (9,000 at SF1); ``i_rec_start_date`` / ``i_rec_end_date`` are the
  revisions' periods.
* CUSTOMER_DEMOGRAPHICS (9 columns, 1,920,800 rows at every scale): the
  full cross product of 2 genders, 5 marital states, 7 education levels,
  20 purchase estimates, 4 credit ratings and 7 x 7 x 7 dependant counts,
  ``cd_gender`` varying fastest.
* PROMOTION (19 columns, 300 rows): ``p_channel_dmail`` Y or N, every
  other channel flag N (as dsdgen's own rows have them).

Where it is not dsdgen (``assumed`` in ``configs/tpcds_sf1.json``):
numpy's PCG64 streams; NULLs drawn per column and not from a row's
bitmap; the calendar's weights, the ticket sizes and the pricing ranges
are from memory of dsdgen's distributions; text is words of a small
pool.  Each table has a random stream of its own
(``default_rng([seed, stream])``), so a cell generates only the tables
its queries name.
"""

import datetime

import numpy as np
import pyarrow as pa

ROWS_SF1 = {"store_sales": 2_880_404, "item": 18_000, "promotion": 300,
            "date_dim": 73_049, "customer_demographics": 1_920_800,
            # referenced by store_sales' keys only (not generated here)
            "customer": 100_000, "customer_address": 50_000,
            "household_demographics": 7_200, "store": 12}
_FIXED = ("date_dim", "customer_demographics", "promotion",
          "household_demographics", "store")
_STREAMS = {"store_sales": 0, "item": 1, "promotion": 2, "date_dim": 3,
            "customer_demographics": 4}
NULL_SHARE = 0.045          # of every nullable fact column
DIM_NULL_SHARE = {"item": 0.0025, "promotion": 0.01}

GENDERS = ["M", "F"]
MARITAL = ["M", "S", "D", "W", "U"]
EDUCATION = ["Primary", "Secondary", "College", "2 yr Degree",
             "4 yr Degree", "Advanced Degree", "Unknown"]
CREDIT = ["Good", "High Risk", "Low Risk", "Unknown"]
DAY_NAMES = ["Sunday", "Monday", "Tuesday", "Wednesday", "Thursday",
             "Friday", "Saturday"]
CATEGORIES = ["Women", "Men", "Children", "Shoes", "Music", "Jewelry",
              "Home", "Sports", "Books", "Electronics"]
CLASSES = ["accent", "bathroom", "bedding", "blinds/shades", "curtains",
           "decor", "flatware", "furniture", "glassware", "kids",
           "lighting", "mattresses", "paint", "rugs", "tables",
           "wallpaper"]
SIZES = ["petite", "small", "medium", "large", "extra large", "economy",
         "N/A"]
UNITS = ["Unknown", "Each", "Dozen", "Case", "Pallet", "Gross", "Carton",
         "Box", "Bunch", "Bundle", "Cup", "Dram", "Gram", "Lb", "N/A",
         "Ounce", "Oz", "Pound", "Ton", "Tbl", "Tsp"]
COLORS = """almond antique aquamarine azure beige bisque black blanched blue
blush brown burlywood burnished chartreuse chiffon chocolate coral cornflower
cornsilk cream cyan dark deep dim dodger drab firebrick floral forest frosted
gainsboro ghost goldenrod green grey honeydew hot indian ivory khaki lace
lavender lawn lemon light lime linen magenta maroon medium metallic midnight
mint misty moccasin navajo navy olive orange orchid pale papaya peach peru
pink plum powder puff purple red rose rosy royal saddle salmon sandy seashell
sienna sky slate smoke snow spring steel tan thistle tomato turquoise violet
wheat white yellow""".split()
SYLLABLES = ["ought", "able", "pri", "ese", "anti", "cally", "ation",
             "eing", "n st", "bar"]
WORDS = """sales items public years good new high important social general
different national small local early political large young possible economic
likely real major only other special full clear able whole particular
available difficult recent central certain similar common main hard human
present british long military true international private free""".split()
PURPOSES = ["Unknown", "Other", "Charity"]

COLUMNS = {
    "store_sales": [
        "ss_sold_date_sk", "ss_sold_time_sk", "ss_item_sk",
        "ss_customer_sk", "ss_cdemo_sk", "ss_hdemo_sk", "ss_addr_sk",
        "ss_store_sk", "ss_promo_sk", "ss_ticket_number", "ss_quantity",
        "ss_wholesale_cost", "ss_list_price", "ss_sales_price",
        "ss_ext_discount_amt", "ss_ext_sales_price",
        "ss_ext_wholesale_cost", "ss_ext_list_price", "ss_ext_tax",
        "ss_coupon_amt", "ss_net_paid", "ss_net_paid_inc_tax",
        "ss_net_profit"],
    "date_dim": [
        "d_date_sk", "d_date_id", "d_date", "d_month_seq", "d_week_seq",
        "d_quarter_seq", "d_year", "d_dow", "d_moy", "d_dom", "d_qoy",
        "d_fy_year", "d_fy_quarter_seq", "d_fy_week_seq", "d_day_name",
        "d_quarter_name", "d_holiday", "d_weekend", "d_following_holiday",
        "d_first_dom", "d_last_dom", "d_same_day_ly", "d_same_day_lq",
        "d_current_day", "d_current_week", "d_current_month",
        "d_current_quarter", "d_current_year"],
    "item": [
        "i_item_sk", "i_item_id", "i_rec_start_date", "i_rec_end_date",
        "i_item_desc", "i_current_price", "i_wholesale_cost", "i_brand_id",
        "i_brand", "i_class_id", "i_class", "i_category_id", "i_category",
        "i_manufact_id", "i_manufact", "i_size", "i_formulation", "i_color",
        "i_units", "i_container", "i_manager_id", "i_product_name"],
    "customer_demographics": [
        "cd_demo_sk", "cd_gender", "cd_marital_status",
        "cd_education_status", "cd_purchase_estimate", "cd_credit_rating",
        "cd_dep_count", "cd_dep_employed_count", "cd_dep_college_count"],
    "promotion": [
        "p_promo_sk", "p_promo_id", "p_start_date_sk", "p_end_date_sk",
        "p_item_sk", "p_cost", "p_response_target", "p_promo_name",
        "p_channel_dmail", "p_channel_email", "p_channel_catalog",
        "p_channel_tv", "p_channel_radio", "p_channel_press",
        "p_channel_event", "p_channel_demo", "p_channel_details",
        "p_purpose", "p_discount_active"],
}
MONEY = COLUMNS["store_sales"][11:]
# the primary key's columns: never NULL
NOT_NULL = {"ss_item_sk", "ss_ticket_number"}

EPOCH = datetime.date(1970, 1, 1)
JULIAN_EPOCH = 2440588            # the Julian day number of 1970-01-01


def _days(year, month, day):
    return (datetime.date(year, month, day) - EPOCH).days


FIRST_DATE = _days(1900, 1, 2)    # d_date_sk 2415022
SALES_FIRST, SALES_LAST = _days(1998, 1, 2), _days(2002, 12, 31)
# the periods of an item's revisions (start of the 2nd of 2; 2nd and 3rd
# of 3), and the first revision's start
REV_START = _days(1997, 10, 27)
REV2_OF2 = _days(2000, 10, 27)
REV2_OF3, REV3_OF3 = _days(1999, 10, 28), _days(2001, 10, 27)


def rows(table, sf):
    """Rows of ``table`` at scale factor ``sf``; the fixed tables are the
    same at every scale, item keeps whole groups of six revisions."""
    if table in _FIXED:
        return ROWS_SF1[table]
    if table == "item":
        return 6 * max(int(ROWS_SF1["item"] * sf) // 6, 10)
    return max(int(ROWS_SF1[table] * sf), 200)


def item_ids(sf):
    """Distinct ``i_item_id`` (business keys) at ``sf``."""
    return rows("item", sf) // 2


def _rng(seed, stream):
    return np.random.default_rng([int(seed), _STREAMS[stream]])


# ------------------------------------------------------------ arrow bits --

def _bitmap(valid):
    return pa.py_buffer(np.packbits(valid, bitorder="little"))


def _int32(values, null=None):
    """An int32 column of ``values``, NULL where ``null`` is set."""
    return pa.array(np.asarray(values, dtype=np.int32), mask=null)


def _decimal(cents, precision, null=None):
    """``decimal(precision, 2)`` from whole cents, in bulk: the 128-bit
    words are the int64 and its sign."""
    cents = np.ascontiguousarray(cents, dtype=np.int64)
    words = np.empty((len(cents), 2), dtype=np.int64)
    words[:, 0], words[:, 1] = cents, cents >> 63
    if null is None or not null.any():
        validity, nulls = None, 0
    else:
        validity, nulls = _bitmap(~null), int(null.sum())
    return pa.Array.from_buffers(pa.decimal128(precision, 2), len(cents),
                                 [validity, pa.py_buffer(words)], nulls)


def _coded(codes, values, null=None):
    """A plain string column from codes into ``values``."""
    codes = pa.array(np.asarray(codes, dtype=np.int32), mask=null)
    return pa.DictionaryArray.from_arrays(
        codes, pa.array(values, pa.string())).cast(pa.string())


def _dates(days, null=None):
    return pa.array(np.asarray(days, dtype=np.int32), mask=null) \
        .view(pa.date32())


def _bkeys(keys):
    """The 16-character business key of a surrogate key: eight ``A`` for
    the high word, then the low word's hexadecimal digits as ``A``..``P``,
    lowest first (1 -> ``AAAAAAAABAAAAAAA``)."""
    keys = np.asarray(keys, dtype=np.int64)
    chars = np.full((len(keys), 16), ord("A"), dtype=np.uint8)
    for i in range(8):
        chars[:, 8 + i] += ((keys >> (4 * i)) & 15).astype(np.uint8)
    offsets = np.arange(len(keys) + 1, dtype=np.int32) * 16
    return pa.StringArray.from_buffers(
        len(keys), pa.py_buffer(offsets), pa.py_buffer(chars))


def _phrases(rng, n, lo, hi, words=WORDS):
    """``n`` strings of ``lo``..``hi`` words of the pool."""
    pool = np.array(words)
    out = pool[rng.integers(0, len(pool), n)]
    count = rng.integers(lo, hi + 1, n)
    for k in range(1, hi):
        more = np.char.add(" ", pool[rng.integers(0, len(pool), n)])
        out = np.char.add(out, np.where(count > k, more, ""))
    return out


def _nulls(rng, n, share):
    return rng.random(n) < share


# ---------------------------------------------------------------- tables --

def item_revision(business_key, day):
    """``i_item_sk`` of the revision of ``business_key`` (0-based) that is
    valid on ``day`` (days since 1970): keys take 1, 2, 3 revisions in
    turn, so three keys fill six surrogate keys."""
    group, kind = np.divmod(np.asarray(business_key, dtype=np.int64), 3)
    first = 6 * group + np.array([0, 1, 3])[kind] + 1
    rev = np.where(kind == 1, day >= REV2_OF2,
                   np.where(kind == 2,
                            (day >= REV2_OF3).astype(np.int64)
                            + (day >= REV3_OF3), 0))
    return first + rev


def sales_day_weights():
    """(days, weights) of the sales calendar: every day of 1998-01-02 ..
    2002-12-31, August to October twice and November and December three
    times as likely as a day of January to July."""
    days = np.arange(SALES_FIRST, SALES_LAST + 1)
    month = (days.astype("datetime64[D]").astype("datetime64[M]")
             .astype(np.int64) % 12) + 1
    weight = np.where(month >= 11, 3.0, np.where(month >= 8, 2.0, 1.0))
    return days, weight / weight.sum()


def _store_sales(sf, seed):
    rng, n = _rng(seed, "store_sales"), rows("store_sales", sf)
    # tickets of 8..16 lines until the rows are full; the last is cut
    per = rng.integers(8, 17, n // 8 + 1)
    n_tickets = int(np.searchsorted(np.cumsum(per), n) + 1)
    per = per[:n_tickets]
    per[-1] -= int(per.sum()) - n
    ticket = np.repeat(np.arange(n_tickets), per)
    line = np.arange(n) - np.repeat(np.cumsum(per) - per, per)

    days, weights = sales_day_weights()
    day = np.sort(rng.choice(days, n_tickets, p=weights))
    n_ids = item_ids(sf)
    permutation = rng.permutation(n_ids)
    start = rng.integers(0, n_ids, n_tickets)
    business_key = permutation[(start[ticket] + line) % n_ids]

    def per_ticket(hi, lo=1):
        return rng.integers(lo, hi + 1, n_tickets)[ticket]

    keys = {
        "ss_sold_date_sk": (day + JULIAN_EPOCH)[ticket],
        "ss_sold_time_sk": per_ticket(75_599, 28_800),
        "ss_item_sk": item_revision(business_key, day[ticket]),
        "ss_customer_sk": per_ticket(rows("customer", sf)),
        "ss_cdemo_sk": per_ticket(ROWS_SF1["customer_demographics"]),
        "ss_hdemo_sk": per_ticket(ROWS_SF1["household_demographics"]),
        "ss_addr_sk": per_ticket(rows("customer_address", sf)),
        "ss_store_sk": per_ticket(ROWS_SF1["store"]),
        "ss_promo_sk": rng.integers(1, ROWS_SF1["promotion"] + 1, n),
    }
    # pricing in whole cents, halves rounded up
    quantity = rng.integers(1, 101, n)
    wholesale = rng.integers(100, 10_001, n)
    markup = rng.integers(0, 201, n)            # percent of wholesale
    discount = rng.integers(0, 101, n)          # percent of list
    list_price = (wholesale * (100 + markup) + 50) // 100
    sales_price = (list_price * (100 - discount) + 50) // 100
    ext_sales = sales_price * quantity
    ext_list = list_price * quantity
    coupon_pct = np.where(rng.integers(0, 5, n) == 0,
                          rng.integers(0, 101, n), 0)
    coupon = (ext_sales * coupon_pct + 50) // 100
    net_paid = ext_sales - coupon
    tax = (net_paid * rng.integers(0, 10, n) + 50) // 100
    money = {
        "ss_wholesale_cost": wholesale, "ss_list_price": list_price,
        "ss_sales_price": sales_price,
        "ss_ext_discount_amt": ext_list - ext_sales,
        "ss_ext_sales_price": ext_sales,
        "ss_ext_wholesale_cost": wholesale * quantity,
        "ss_ext_list_price": ext_list, "ss_ext_tax": tax,
        "ss_coupon_amt": coupon, "ss_net_paid": net_paid,
        "ss_net_paid_inc_tax": net_paid + tax,
        "ss_net_profit": net_paid - wholesale * quantity,
    }

    def null(name):
        if name in NOT_NULL:
            return None
        return _nulls(rng, n, NULL_SHARE)

    columns = {}
    for name in COLUMNS["store_sales"]:
        if name in keys:
            columns[name] = _int32(keys[name], null(name))
        elif name == "ss_ticket_number":
            columns[name] = pa.array((ticket + 1).astype(np.int64))
        elif name == "ss_quantity":
            columns[name] = _int32(quantity, null(name))
        else:
            columns[name] = _decimal(money[name], 7, null(name))
    return pa.table(columns)


def _date_dim(sf, seed):
    n = ROWS_SF1["date_dim"]
    days = FIRST_DATE + np.arange(n)
    d = days.astype("datetime64[D]")
    months = d.astype("datetime64[M]")
    year = d.astype("datetime64[Y]").astype(np.int64) + 1970
    moy = months.astype(np.int64) % 12 + 1
    dom = (d - months).astype(np.int64) + 1
    qoy = (moy - 1) // 3 + 1
    dow = (days + 4) % 7                        # 1970-01-01: a Thursday
    sk = days + JULIAN_EPOCH
    first_dom = months.astype("datetime64[D]").astype(np.int64)
    last_dom = (months + 1).astype("datetime64[D]").astype(np.int64) - 1
    month_seq = (year - 1900) * 12 + moy - 1
    quarter_seq = (year - 1900) * 4 + qoy
    week_seq = (days - FIRST_DATE + 1) // 7 + 1
    holiday = ((moy == 1) & (dom == 1)) | ((moy == 7) & (dom == 4)) \
        | ((moy == 12) & (dom == 25))
    today = _days(2003, 1, 8)
    yes_no = ["N", "Y"]

    def same(what):
        return what & (year == 2003)

    quarter_name = np.char.add(np.char.add(year.astype(str), "Q"),
                               qoy.astype(str))
    numbers = {
        "d_date_sk": sk, "d_month_seq": month_seq, "d_week_seq": week_seq,
        "d_quarter_seq": quarter_seq, "d_year": year, "d_dow": dow,
        "d_moy": moy, "d_dom": dom, "d_qoy": qoy, "d_fy_year": year,
        "d_fy_quarter_seq": quarter_seq, "d_fy_week_seq": week_seq,
        "d_first_dom": first_dom + JULIAN_EPOCH,
        "d_last_dom": last_dom + JULIAN_EPOCH,
        "d_same_day_ly": sk - 365, "d_same_day_lq": sk - 91,
    }
    columns = {name: _int32(values) for name, values in numbers.items()}
    columns.update({
        "d_date_id": _bkeys(sk), "d_date": _dates(days),
        "d_day_name": _coded(dow, DAY_NAMES),
        "d_quarter_name": pa.array(quarter_name, pa.string()),
        "d_holiday": _coded(holiday, yes_no),
        "d_weekend": _coded((dow == 0) | (dow == 6), yes_no),
        "d_following_holiday": _coded(np.roll(holiday, 1), yes_no),
        "d_current_day": _coded(days == today, yes_no),
        "d_current_week": _coded(
            same(week_seq == week_seq[today - FIRST_DATE]), yes_no),
        "d_current_month": _coded(same(moy == 1), yes_no),
        "d_current_quarter": _coded(same(qoy == 1), yes_no),
        "d_current_year": _coded(year == 2003, yes_no),
    })
    return pa.table({name: columns[name] for name in COLUMNS["date_dim"]})


def _item(sf, seed):
    rng, n = _rng(seed, "item"), rows("item", sf)
    sk = np.arange(1, n + 1)
    group, slot = np.divmod(sk - 1, 6)
    kind = np.array([0, 1, 1, 2, 2, 2])[slot]        # revisions - 1
    rev = np.array([0, 0, 1, 0, 1, 2])[slot]
    first = 6 * group + np.array([0, 1, 1, 3, 3, 3])[slot] + 1
    starts = np.array([[REV_START, 0, 0], [REV_START, REV2_OF2, 0],
                       [REV_START, REV2_OF3, REV3_OF3]])
    start = starts[kind, rev]
    last = rev == kind
    end = np.where(last, 0, starts[kind, np.minimum(rev + 1, 2)] - 1)
    share = DIM_NULL_SHARE["item"]

    def null():
        return _nulls(rng, n, share)

    wholesale = rng.integers(2, 8_800, n)
    price = (wholesale * rng.integers(110, 300, n) + 50) // 100
    category = rng.integers(0, len(CATEGORIES), n)
    klass = rng.integers(0, len(CLASSES), n)
    brand = rng.integers(1, 11, n)
    manufact = rng.integers(1, 1001, n)
    sy = np.array(SYLLABLES)

    def syllables(number):
        """dsdgen's number names: a syllable a decimal digit."""
        out = sy[number % 10]
        rest = number // 10
        while rest.any():
            out = np.where(rest > 0, np.char.add(out, sy[rest % 10]), out)
            rest = rest // 10
        return out

    brand_name = np.char.add(np.char.add(
        np.array(["import", "edu pack", "amalg", "expor", "schola",
                  "corp", "brand", "univ", "maxi", "nameless"])[
                      category], np.array(["o", "", "", "ti", "r"])[
                          klass % 5]), np.char.add(" #", brand.astype(str)))
    return pa.table({
        "i_item_sk": _int32(sk),
        "i_item_id": _bkeys(first),
        "i_rec_start_date": _dates(start, null()),
        "i_rec_end_date": _dates(end, last | null()),
        "i_item_desc": pa.array(_phrases(rng, n, 2, 24), pa.string(),
                                mask=null()),
        "i_current_price": _decimal(price, 7, null()),
        "i_wholesale_cost": _decimal(wholesale, 7, null()),
        "i_brand_id": _int32((category + 1) * 1_000_000
                             + (klass + 1) * 1_000 + brand, null()),
        "i_brand": pa.array(brand_name, pa.string(), mask=null()),
        "i_class_id": _int32(klass + 1, null()),
        "i_class": _coded(klass, CLASSES, null()),
        "i_category_id": _int32(category + 1, null()),
        "i_category": _coded(category, CATEGORIES, null()),
        "i_manufact_id": _int32(manufact, null()),
        "i_manufact": pa.array(syllables(manufact), pa.string(),
                               mask=null()),
        "i_size": _coded(rng.integers(0, len(SIZES), n), SIZES, null()),
        "i_formulation": pa.array(np.char.add(
            rng.integers(10**9, 10**10, n).astype(str),
            np.array(COLORS)[rng.integers(0, len(COLORS), n)]),
            pa.string(), mask=null()),
        "i_color": _coded(rng.integers(0, len(COLORS), n), COLORS, null()),
        "i_units": _coded(rng.integers(0, len(UNITS), n), UNITS, null()),
        "i_container": _coded(np.zeros(n, dtype=np.int32), ["Unknown"],
                              null()),
        "i_manager_id": _int32(rng.integers(1, 101, n), null()),
        "i_product_name": pa.array(syllables(sk), pa.string(),
                                   mask=null()),
    })


def _customer_demographics(sf, seed):
    n = ROWS_SF1["customer_demographics"]
    k = np.arange(n)
    sizes = [2, 5, 7, 20, 4, 7, 7, 7]
    digits = []
    for size in sizes:
        k, digit = np.divmod(k, size)
        digits.append(digit)
    return pa.table({
        "cd_demo_sk": _int32(np.arange(1, n + 1)),
        "cd_gender": _coded(digits[0], GENDERS),
        "cd_marital_status": _coded(digits[1], MARITAL),
        "cd_education_status": _coded(digits[2], EDUCATION),
        "cd_purchase_estimate": _int32((digits[3] + 1) * 500),
        "cd_credit_rating": _coded(digits[4], CREDIT),
        "cd_dep_count": _int32(digits[5]),
        "cd_dep_employed_count": _int32(digits[6]),
        "cd_dep_college_count": _int32(digits[7]),
    })


def _promotion(sf, seed):
    rng, n = _rng(seed, "promotion"), ROWS_SF1["promotion"]
    sk = np.arange(1, n + 1)
    share = DIM_NULL_SHARE["promotion"]

    def null():
        return _nulls(rng, n, share)

    def flag(codes):
        return _coded(codes, ["N", "Y"], null())

    never = np.zeros(n, dtype=np.int32)
    start = rng.integers(SALES_FIRST, SALES_LAST - 60, n)
    return pa.table({
        "p_promo_sk": _int32(sk),
        "p_promo_id": _bkeys(sk),
        "p_start_date_sk": _int32(start + JULIAN_EPOCH, null()),
        "p_end_date_sk": _int32(start + rng.integers(1, 61, n)
                                + JULIAN_EPOCH, null()),
        "p_item_sk": _int32(rng.integers(1, rows("item", sf) + 1, n),
                            null()),
        "p_cost": _decimal(np.full(n, 100_000), 15, null()),
        "p_response_target": _int32(np.ones(n), null()),
        "p_promo_name": _coded(sk % len(SYLLABLES), SYLLABLES, null()),
        "p_channel_dmail": flag(rng.integers(0, 2, n)),
        "p_channel_email": flag(never),
        "p_channel_catalog": flag(never),
        "p_channel_tv": flag(never),
        "p_channel_radio": flag(never),
        "p_channel_press": flag(never),
        "p_channel_event": flag(never),
        "p_channel_demo": flag(never),
        "p_channel_details": pa.array(_phrases(rng, n, 3, 12),
                                      pa.string(), mask=null()),
        "p_purpose": _coded(np.zeros(n, dtype=np.int32), PURPOSES, null()),
        "p_discount_active": flag(never),
    })


_TABLES = {"store_sales": _store_sales, "date_dim": _date_dim,
           "item": _item, "customer_demographics": _customer_demographics,
           "promotion": _promotion}


def gen_table(name, sf, seed):
    """One table as an Arrow table; the same (name, sf, seed) gives the
    same rows whatever else is generated."""
    return _TABLES[name](float(sf), int(seed))


def gen_tables(names, sf, seed):
    return {name: gen_table(name, sf, seed) for name in names}
