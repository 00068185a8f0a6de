"""How a number compared becomes a check, and the limits that no
configuration owns.

A limit sits above the largest value sound runs of the program gave and below
the smallest the control gave (the same path in the nearest lower precision),
with room on both sides. A training configuration carries its limits in its
own file (``check_limits``); PERF.md section 2 has the readings of each.
"""

SERVE = {
    # a served probability against forest_reference's: sound runs read at most
    # 2.6e-7 (12 runs), thresholds and leaves in bfloat16 at least 0.090
    # (4 seeds, 4,096 rows x 500 trees) (my chip runs, PR 25; PERF.md section 7)
    "served_prob_gap": 1e-5,
}


def check(name, value, limit):
    """One number compared. ``limit`` None: printed for the record, not judged."""
    return {
        "name": name,
        "value": value,
        "limit": limit,
        "ok": True if limit is None else bool(value <= limit),
    }
