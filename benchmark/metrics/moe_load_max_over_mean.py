"""How unevenly the router spread the last step's pairs over the experts
held here: over every expert layer, the fullest held expert's load over the
mean held expert's (source: the ``moe_load`` counter the program hands to
``mxnet_tpu.telemetry`` when the step drains at close; aux state on the
device until then).  Nothing where the program keeps no such counter (a
parent commit) or the model has no expert layer."""


def read(obs, args):
    from mxnet_tpu import telemetry

    loads = getattr(telemetry, "moe_load", None)
    if loads is None:
        return None
    rows = [v for k, v in loads().items() if k.endswith("_load") and sum(v)]
    if not rows:
        return None
    return max(max(v) / (sum(v) / len(v)) for v in rows)
