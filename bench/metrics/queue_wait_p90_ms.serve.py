"""queue_wait_p90_ms.serve: 90th percentile, over the window's requests, of
the time from a request's due time to the start of the ``Server.step`` call
that admitted it (host clock)."""


def read(obs: dict):
    return obs.get("queue_wait_p90_ms")
