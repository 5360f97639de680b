"""Host seconds of the session's first `.fused` access: `partition_plan`
and the jitted exchange's build."""


def read(rec):
    return rec["setup"].get("partition")
