"""The program's own time on a `POST /jobs/prove` (since PR 34): the
movement of `http_server_seconds_total{route="/jobs/prove"}` over the
movement of `http_server_requests_total{route="/jobs/prove"}`, between the
/metrics text taken after the warm-up and the one taken after the window,
in ms: the handler's wall, from the middleware's entry to the 202 (the
multipart read, admission, the queue hand-off). `submit_ms` times the same
request on the client's clock; the difference is the client's and the
socket's share. Only the one route is read (`_counters._total` would sum
every route's series, and a route template's braces are not what its
pattern expects). None where the program has no such counter, as the
parent of that PR has not, or the window moved no request there."""

import re

LAYER, UNIT, MOVES = "front door", "ms", "proof_p50_s"
ROUTE = "/jobs/prove"


def _series(text, family):
    """The one series of `family` whose `route` is ROUTE, or None."""
    found = re.findall(
        rf'^{family}\{{route="{re.escape(ROUTE)}"\}}\s+([-+0-9.eE]+)$',
        text or "", re.M,
    )
    return float(found[0]) if found else None


def read(run):
    rec = run.get("records") or {}
    moved = []
    for family in ("http_server_seconds_total", "http_server_requests_total"):
        after = _series(rec.get("metrics_after"), family)
        if after is None:
            return None
        moved.append(after - (_series(rec.get("metrics_before"), family) or 0))
    seconds, requests = moved
    return 1e3 * seconds / requests if requests > 0 else None
