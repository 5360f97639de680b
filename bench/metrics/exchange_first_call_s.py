"""Seconds of set-up in the program's `exchange.first_call` span: the
warm-up's first call of the jitted exchange, which traces and compiles it
or loads it from the compile cache."""


def read(rec):
    return rec["setup"].get("span:exchange.first_call")
