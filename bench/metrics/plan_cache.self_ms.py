"""Plan cache (``serve/query_service.py``): the ``query.execute`` span
less its ``storage.load_part`` children, in ms per request: fingerprint,
lookup, parameter binding and the dispatch of the cached executable."""

from harness import spans


def read(obs):
    if not obs.spans:
        return None
    execs = [s for t in obs.spans for s in spans.find(t, "query.execute")]
    if not execs:
        return None
    return sum(spans.self_ms(s, only=("storage.load_part",))
               for s in execs) / len(obs.spans)
