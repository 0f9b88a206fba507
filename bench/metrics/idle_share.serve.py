"""idle_share.serve: the share of the traced window in which no operation
ran on the device (1 - the union of busy intervals over the window), in
percent."""


def read(obs: dict):
    share = obs.get("trace", {}).get("idle_share")
    return None if share is None else 100.0 * share
