"""Seconds of set-up in the program's `plan.edge_tables` span: binding the
plan to the CSR (locating every scheduled value's entry and the Reduce
gather), after the `engine.compile` span."""


def read(rec):
    return rec["setup"].get("span:plan.edge_tables")
