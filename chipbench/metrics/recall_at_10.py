"""recall_at_10: mean recall@10 of every answer of the window against the
exact reference (chipbench/reference.py)."""


def read(run):
    if run.recall is None or not len(run.recall):
        return None
    return float(run.recall.mean())
