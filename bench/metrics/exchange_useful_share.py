"""Percent of the bits the chips put into the exchange's collectives that
the coded schedule needs (Definition 2 load): the program's
`exchange_bits_useful_total` over `exchange_bits_sent_total` counters."""
from repro.obs import get_registry


def read(rec):
    reg = get_registry()
    useful = reg.get("exchange_bits_useful_total")
    sent = reg.get("exchange_bits_sent_total")
    if not useful or not sent or not sent.value:
        return None
    return 100.0 * useful.value / sent.value
