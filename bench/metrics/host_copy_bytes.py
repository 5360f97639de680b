"""Bytes copied between host and chips per exchange: the local words placed
on the chips plus the padded deliveries pulled back, from the program's
`exchange_host_copy_bytes_total` over `exchange_calls_total` counters."""
from repro.obs import get_registry


def read(rec):
    reg = get_registry()
    calls = reg.get("exchange_calls_total")
    copied = reg.get("exchange_host_copy_bytes_total")
    if not calls or not copied or not calls.value:
        return None
    return copied.value / calls.value
