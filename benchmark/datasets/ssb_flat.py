"""Star Schema Benchmark lineorder, flattened into one index.

Source: O'Neil, O'Neil, Chen, "Star Schema Benchmark", rev. 3 (2009),
section 2 (the LINEORDER, PART, SUPPLIER, CUSTOMER and DATE columns and
their domains), denormalised into one record per lineorder row the way
upstream Pilosa published it (github.com/pilosa/demo-ssb). Written from
memory of both; what this file sets itself is listed under ``assumed`` in
the configuration files that name this dataset.

A dataset module gives the harness three things and nothing else:

``INDEX``            the index name
``fields()``         the schema, one dict per field
``make(seed, stream, count)``  ``count`` records as numpy columns

Bitmap fields (``mutex``/``set``) hold a *slot* per record, 0..rows-1;
``ids`` (row ids) or ``keys`` (row keys) say what slot ``i`` is called on
the wire. Int fields hold the value itself. The same ``(seed, stream)``
gives the same records: stream ``s`` is shard ``s`` of the loaded table,
streams from ``INGEST_STREAM`` up are the batches a writer appends.
"""

import numpy as np

INDEX = "ssb"
INGEST_STREAM = 1 << 16

REGIONS = ("AFRICA", "AMERICA", "ASIA", "EUROPE", "MIDDLE EAST")
#: TPC-H's 25 nations, ordered so that nation // 5 is its region
NATIONS = (
    "ALGERIA", "ETHIOPIA", "KENYA", "MOROCCO", "MOZAMBIQUE",
    "ARGENTINA", "BRAZIL", "CANADA", "PERU", "UNITED STATES",
    "CHINA", "INDIA", "INDONESIA", "JAPAN", "VIETNAM",
    "FRANCE", "GERMANY", "ROMANIA", "RUSSIA", "UNITED KINGDOM",
    "EGYPT", "IRAN", "IRAQ", "JORDAN", "SAUDI ARABIA",
)
#: SSB city = the nation's first nine characters, padded, plus a digit
CITIES = tuple(f"{n[:9]:<9}{d}" for n in NATIONS for d in range(10))
#: SSB brand MFGR#<mfgr><category><1..40>: 5 x 5 x 40 = 1000
BRANDS = tuple(f"MFGR#{m}{c}{b}" for m in range(1, 6) for c in range(1, 6)
               for b in range(1, 41))
YEARS = tuple(range(1992, 1999))
_DAYS = np.arange(np.datetime64("1992-01-01"), np.datetime64("1999-01-01"))
_DAY_YEAR = (_DAYS.astype("datetime64[Y]").astype(np.int64) + 1970
             - YEARS[0]).astype(np.int8)
_DAY_MONTH = (_DAYS.astype("datetime64[M]").astype(np.int64) % 12
              ).astype(np.int8)
_DAY_WEEK = ((_DAYS - _DAYS.astype("datetime64[Y]")).astype(np.int64) // 7
             ).astype(np.int8)


def _mutex(name, rows, ids=None, keys=None):
    return {"name": name, "type": "mutex", "rows": rows,
            "ids": list(ids) if ids is not None else None,
            "keys": list(keys) if keys is not None else None}


def _int(name, lo, hi):
    return {"name": name, "type": "int", "min": lo, "max": hi}


def fields():
    return [
        _mutex("lo_year", 7, ids=YEARS),
        _mutex("lo_month", 12, ids=range(1, 13)),
        _mutex("lo_weeknum", 53, ids=range(1, 54)),
        _int("lo_quantity", 1, 50),
        _int("lo_discount", 0, 10),
        _int("lo_extendedprice", 900, 104_950),
        _int("lo_revenue", 810, 104_950),
        _int("lo_supplycost", 540, 1_259),
        _int("lo_profit", 0, 104_410),
        _int("lo_revenue_computed", 0, 1_049_500),
        _mutex("p_mfgr", 5, ids=range(1, 6)),
        _mutex("p_category", 25, ids=range(25)),
        _mutex("p_brand1", 1000, keys=BRANDS),
        _mutex("c_region", 5, ids=range(5)),
        _mutex("c_nation", 25, ids=range(25)),
        _mutex("c_city", 250, keys=CITIES),
        _mutex("s_region", 5, ids=range(5)),
        _mutex("s_nation", 25, ids=range(25)),
        _mutex("s_city", 250, keys=CITIES),
        dict(_mutex("lo_shipmode", 7, ids=range(7)), type="set"),
    ]


def make(seed, stream, count):
    """``count`` records of stream ``stream``: every domain uniform, as
    SSB's dbgen draws them; hierarchies consistent (a brand has one
    category and manufacturer, a city one nation and region, a day one
    year, month and week)."""
    rng = np.random.default_rng([int(seed), int(stream)])
    day = rng.integers(0, _DAYS.size, count)
    brand = rng.integers(0, 1000, count).astype(np.int16)
    c_city = rng.integers(0, 250, count).astype(np.int16)
    s_city = rng.integers(0, 250, count).astype(np.int16)
    quantity = rng.integers(1, 51, count).astype(np.int32)
    discount = rng.integers(0, 11, count).astype(np.int32)
    # P_RETAILPRICE spans 900.00-2098.99 in dbgen; whole dollars here
    price = rng.integers(900, 2100, count).astype(np.int32)
    extended = quantity * price
    revenue = extended * (100 - discount) // 100
    supplycost = 6 * price // 10
    return {
        "lo_year": _DAY_YEAR[day],
        "lo_month": _DAY_MONTH[day],
        "lo_weeknum": _DAY_WEEK[day],
        "lo_quantity": quantity,
        "lo_discount": discount,
        "lo_extendedprice": extended,
        "lo_revenue": revenue,
        "lo_supplycost": supplycost,
        "lo_profit": revenue - supplycost,
        "lo_revenue_computed": extended * discount,
        "p_mfgr": (brand // 200).astype(np.int8),
        "p_category": (brand // 40).astype(np.int8),
        "p_brand1": brand,
        "c_region": (c_city // 50).astype(np.int8),
        "c_nation": (c_city // 10).astype(np.int8),
        "c_city": c_city,
        "s_region": (s_city // 50).astype(np.int8),
        "s_nation": (s_city // 10).astype(np.int8),
        "s_city": s_city,
        "lo_shipmode": rng.integers(0, 7, count).astype(np.int8),
    }
