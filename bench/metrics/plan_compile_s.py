"""Seconds in the program's `engine.compile` span: the ShufflePlan compile
of set-up (edge tables come after the span)."""


def read(rec):
    return rec["setup"].get("span:engine.compile")
