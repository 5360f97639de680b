"""Device seconds per iteration of the exchange's decode (decode and strip
gathers, folds): ops under the `exchange.decode` scope, slowest chip."""
from bench import scopes


def read(rec):
    return scopes.device_seconds(rec, "exchange.decode")
