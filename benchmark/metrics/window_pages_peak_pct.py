"""The most pages of the WINDOW layer group that live slots mapped at once,
over the pages of its pool (the gauges ``serving.kv_group_pages_peak.window``
and ``serving.kv_group_pages_total.window`` as the window left them; the
peak is since the store was built, and set-up's requests run one at a
time): whether the planner's split of the byte budget fits the traffic.
Near 100 the pool decides admissions; far under it the bytes would serve
the other group better.  A store without layer groups has no such gauge."""
LAYER = "serving"
UNIT = "%"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "tpot_p90_ms"


def peak_pct(run, group):
    after = run.counters_after
    peak = after.get(f"serving.kv_group_pages_peak.{group}", {}).get("value")
    total = after.get(f"serving.kv_group_pages_total.{group}", {}).get(
        "value")
    if not peak or not total:
        return None
    return 100.0 * peak / total


def read(run):
    return peak_pct(run, "window")
