"""prefilter_share: the share of the engine's queries (padding rows
included) that its §5.2 router sent to the pre-filter route in the
window, from the engine's own counters."""


def read(run):
    stats = run.window.engine_stats
    if not stats.get("queries"):
        return None
    return stats["prefilter_routed"] / stats["queries"]
