"""Tiny CPU sizes of the cells added after ``_tiny.SCALE`` was written."""

import _tiny

_tiny.SCALE.setdefault("ycsbf_zipf_lat", {
    "schema": _tiny.YCSB,
    "traffic": {"rate_per_s": 200, "warm_max_cut": 16, "max_batch": 16}})
