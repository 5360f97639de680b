"""Device seconds per iteration of the exchange's encode (local gather,
shift/mask, XOR kernel): ops under the `exchange.encode` scope, slowest
chip."""
from bench import scopes


def read(rec):
    return scopes.device_seconds(rec, "exchange.encode")
