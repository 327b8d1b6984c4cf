"""NYC taxi rides, one ride a record, bucketed as upstream Pilosa indexed them.

Source: the New York City Taxi & Limousine Commission trip records,
January 2009 to June 2016, about 1.1 billion rides, in the schema of
upstream Pilosa's transportation use case (the ``pilosa/pdk``
``usecase/taxi`` importer and the "Transportation" page of the Pilosa
documentation: one ride a column, every attribute a bucketed row), asked
the four queries of M. Litwintschik's "Billion Taxi Rides" benchmark.
Written from memory of both; what this file sets itself is listed under
``assumed`` in the configuration file that names this dataset.

The contract is ``datasets/ssb_flat.py``'s: ``INDEX``, ``fields()`` and
``make(seed, stream, count)``; a bitmap field holds a slot per record, an
int field the value. What differs from that table is what this one is
here for. The rides are *in time order*: stream ``s`` is shard ``s`` of
the loaded table and holds the ``s``-th of ``CHIP_SHARDS`` equal slices
of the rides, a ride's place in the table fixes its pickup second up to
a jitter of minutes, so a shard spans about six weeks and a year, month,
day-of-month or weekday row is a few runs of columns. And the value
fields are *skewed*: one ``passenger_count`` row holds 70% of the rides
and three hold a few in a million, short trips dominate ``dist_miles``,
green cabs do not exist before August 2013.
"""

import numpy as np

INDEX = "taxi"
INGEST_STREAM = 1 << 16

SHARD_WIDTH = 1 << 20
#: the published table: about 1.1 billion rides, 1,049 shards of 2^20
#: columns over sixteen one-chip nodes; this chip's share is 66 of them
TABLE_SHARDS = 1049
CLUSTER_NODES = 16
CHIP_SHARDS = 66

YEARS = tuple(range(2009, 2017))
#: rides a year in millions, yellow + green, 2016 to the end of June
YEAR_RIDES = (170.0, 169.0, 177.0, 178.0, 173.0, 165.0 + 16.0,
              146.0 + 19.0, 70.0)
_T0 = np.datetime64("2009-01-01")
_END = np.datetime64("2016-07-01")          # the loaded table ends here
_LAST = np.datetime64("2017-01-01")         # the calendar's rows end here
_DAYS = _T0 + np.arange(int((_LAST - _T0).astype(np.int64)) + 1)
_DAY_YEAR = (_DAYS.astype("datetime64[Y]").astype(np.int64) + 1970
             - YEARS[0]).astype(np.int8)
_DAY_MONTH = (_DAYS.astype("datetime64[M]").astype(np.int64) % 12
              ).astype(np.int8)
_DAY_MDAY = (_DAYS - _DAYS.astype("datetime64[M]")).astype(np.int64
                                                           ).astype(np.int8)
# 1970-01-01 was a Thursday; weekday 0 = Monday
_DAY_WEEKDAY = ((_DAYS.astype(np.int64) + 3) % 7).astype(np.int8)


def _day(date):
    return int((np.datetime64(date) - _T0).astype(np.int64))


#: the timeline as a piecewise-linear map from a ride's share of the table
#: (0..1) to days since 2009-01-01: constant rate within a year
_YEAR_START = np.array([_day(f"{y}-01-01") for y in YEARS] + [_day(_END)],
                       dtype=np.float64)
_YEAR_SHARE = np.concatenate([[0.0], np.cumsum(YEAR_RIDES)
                              / float(np.sum(YEAR_RIDES))])
GREEN_FROM_DAY = _day("2013-08-01")
GREEN_SHARE = 0.08
#: the last second a pickup may fall on: a ride lasts under three hours
#: and its drop-off needs a row of the calendar
_MAX_PICKUP_S = (_day("2016-12-31") + 1) * 86400 - 3 * 3600 - 1
JITTER_S = 300

#: share of a day's rides in each of its 48 half hours: lowest before
#: dawn, a morning shoulder, the evening peak at 18:30-20:00
_HALF_HOUR_WEIGHT = np.array(
    [2.2, 1.9, 1.6, 1.3, 1.1, 0.9, 0.7, 0.6, 0.5, 0.5, 0.6, 0.8,   # 0-6h
     1.2, 1.7, 2.2, 2.6, 2.8, 2.8, 2.7, 2.6, 2.5, 2.5, 2.5, 2.6,   # 6-12h
     2.6, 2.6, 2.6, 2.6, 2.6, 2.6, 2.5, 2.4, 2.5, 2.7, 3.0, 3.2,   # 12-18h
     3.4, 3.5, 3.5, 3.3, 3.1, 3.0, 2.9, 2.9, 2.8, 2.7, 2.6, 2.4])  # 18-24h
_DAY_CDF = np.concatenate([[0.0], np.cumsum(_HALF_HOUR_WEIGHT)
                           / _HALF_HOUR_WEIGHT.sum()])
#: average speed in miles an hour by hour of the day: slow from the
#: morning rush to the evening, fast at night
_HOUR_MPH = np.array([16, 17, 18, 18, 18, 17, 15, 12, 10, 10, 10, 10,
                      10, 10, 10, 10, 10, 10, 10, 11, 12, 13, 14, 15],
                     dtype=np.float64)

#: passenger_count 0..9
PASSENGER_SHARE = (0.01 - 9e-6, 0.705, 0.14, 0.04, 0.02, 0.06, 0.025,
                   3e-6, 3e-6, 3e-6)
MAX_MILES, MAX_MINUTES, MAX_MPH, MAX_DOLLARS = 63, 119, 79, 511
MEDIAN_MILES = 1.7


def _mutex(name, rows, ids):
    return {"name": name, "type": "mutex", "rows": rows,
            "ids": list(ids), "keys": None}


def _int(name, lo, hi):
    return {"name": name, "type": "int", "min": lo, "max": hi}


def fields():
    when = []
    for side in ("pickup", "drop"):
        when += [_mutex(f"{side}_year", 8, YEARS),
                 _mutex(f"{side}_month", 12, range(1, 13)),
                 _mutex(f"{side}_mday", 31, range(1, 32)),
                 _mutex(f"{side}_day", 7, range(7)),
                 _mutex(f"{side}_time", 48, range(48))]
    return [
        # upstream's cab_type (the configuration's assumed): yellow, green
        _mutex("cab", 2, range(2)),
        _mutex("passenger_count", 10, range(10)),
        *when,
        _mutex("dist_miles", MAX_MILES + 1, range(MAX_MILES + 1)),
        _mutex("duration_minutes", MAX_MINUTES + 1, range(MAX_MINUTES + 1)),
        _mutex("speed_mph", MAX_MPH + 1, range(MAX_MPH + 1)),
        _int("total_amount_dollars", 0, MAX_DOLLARS),
    ]


def pickup_seconds(seed, stream, count):
    """Seconds since 2009-01-01 of each ride's pickup. Stream ``s`` below
    ``INGEST_STREAM`` is shard ``s``: the ``s``-th of ``CHIP_SHARDS``
    equal slices of the rides, and within it a ride's place gives its day
    (the years' rates) and its time of day (the diurnal curve), plus a
    jitter of up to ``JITTER_S`` seconds either way. The streams from
    ``INGEST_STREAM`` up go on from the table's end at the rate of its
    last half year."""
    stream, count = int(stream), int(count)
    place = (np.arange(count, dtype=np.float64) + 0.5) / SHARD_WIDTH
    if stream < INGEST_STREAM:
        day = np.interp((stream + place) / CHIP_SHARDS, _YEAR_SHARE,
                        _YEAR_START)
    else:
        per_shard = ((_YEAR_START[-1] - _YEAR_START[-2])
                     / (YEAR_RIDES[-1] / np.sum(YEAR_RIDES) * CHIP_SHARDS))
        day = _YEAR_START[-1] + ((stream - INGEST_STREAM) * count
                                 / SHARD_WIDTH + place) * per_shard
    whole = np.floor(day)
    second = np.interp(day - whole, _DAY_CDF, np.arange(49) * 1800.0)
    jitter = np.random.default_rng([int(seed), stream, 1]).integers(
        -JITTER_S, JITTER_S + 1, count)
    return np.clip(whole.astype(np.int64) * 86400 + second.astype(np.int64)
                   + jitter, 0, _MAX_PICKUP_S)


def _calendar(side, seconds):
    day = seconds // 86400
    return {f"{side}_year": _DAY_YEAR[day],
            f"{side}_month": _DAY_MONTH[day],
            f"{side}_mday": _DAY_MDAY[day],
            f"{side}_day": _DAY_WEEKDAY[day],
            f"{side}_time": (seconds % 86400 // 1800).astype(np.int8)}


def _pick(rng, share, count):
    cdf = np.cumsum(np.asarray(share, dtype=np.float64))
    return np.minimum(np.searchsorted(cdf / cdf[-1], rng.random(count),
                                      side="right"),
                      len(share) - 1).astype(np.int8)


def make(seed, stream, count):
    """``count`` rides of stream ``stream``, pickups ascending up to the
    jitter: the calendar fields follow the ride's place in the table,
    every other field is drawn."""
    rng = np.random.default_rng([int(seed), int(stream)])
    pickup = pickup_seconds(seed, stream, count)
    miles = rng.lognormal(np.log(MEDIAN_MILES), 0.85, count)
    mph = np.clip(_HOUR_MPH[pickup % 86400 // 3600]
                  * rng.lognormal(0.0, 0.3, count), 2.0, 60.0)
    minutes = np.clip(np.rint(miles / mph * 60.0 + rng.random(count)),
                      1, 179).astype(np.int64)
    # the meter: flag drop, per mile, and a tip on two rides in three
    tip = np.where(rng.random(count) < 0.65, 0.25 * rng.random(count), 0.0)
    fare = (3.0 + 2.5 * miles) * (1.0 + tip)
    green = ((pickup // 86400 >= GREEN_FROM_DAY)
             & (rng.random(count) < GREEN_SHARE))
    return {
        "cab": green.astype(np.int8),
        "passenger_count": _pick(rng, PASSENGER_SHARE, count),
        **_calendar("pickup", pickup),
        **_calendar("drop", pickup + minutes * 60),
        "dist_miles": np.minimum(np.rint(miles), MAX_MILES).astype(np.int8),
        "duration_minutes": np.minimum(minutes, MAX_MINUTES
                                       ).astype(np.int8),
        "speed_mph": np.minimum(np.rint(miles * 60.0 / minutes), MAX_MPH
                                ).astype(np.int8),
        "total_amount_dollars": np.minimum(np.rint(fare), MAX_DOLLARS
                                           ).astype(np.int16),
    }
