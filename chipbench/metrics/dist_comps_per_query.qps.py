"""dist_comps_per_query.qps: mean distance computations per answered
query of the window, as the program counts them
(SearchResult.stats["dist_comps"])."""
import numpy as np


def read(run):
    answered = run.answered()
    if not answered:
        return None
    return float(np.mean([r.dist_comps for r in answered]))
