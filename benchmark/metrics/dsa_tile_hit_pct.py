"""The share of the tiles the flash calls under a learned selection visit
that hold at least one selected pair, in %, over every layer of the last
step (source: the ``dsa_tile_hit`` aux reading the program hands to
``mxnet_tpu.telemetry`` when the step drains at close; aux state on the
device until then).  A visited tile that holds none is work a tile skip
would save.  Nothing where the program keeps no such reading (a parent
commit) or the model has no selection."""


def read(obs, args):
    from mxnet_tpu import telemetry

    readings = getattr(telemetry, "aux_readings", None)
    if readings is None:
        return None
    rows = [v[0] for v in readings("dsa_tile_hit").values() if v]
    if not rows:
        return None
    return 100.0 * sum(rows) / len(rows)
