"""Session: ``stage_seconds.bind`` (statement-cache lookup; generic-plan
lookup: normalize, versions, signature walk, match; memory estimate;
caching the runner) per statement answered."""


def read(r):
    n = r.answered()
    return (r.hist("stage_seconds.bind")[1]) / n * 1e3 if n else 0.0
