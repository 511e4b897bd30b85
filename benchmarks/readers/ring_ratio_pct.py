"""A ratio of sums over the window's decode ticks of fields of the
program's ``ENG_ROUTE`` records (``_route.FIELDS``), in percent.
``"capacity"`` in ``denom`` stands for the held experts of all expert
layers, once a tick (from the configuration)."""
from benchmarks.readers import _route


def read(ctx, numer: str, denom: list):
    routes = _route.decode_routes(ctx)
    if routes is None or not len(routes):
        return None
    c = ctx.config
    sparse = c["mlp_layer_types"][:c["serve"]["num_hidden_layers"]].count(
        "sparse")
    total = 0.0
    for name in denom:
        total += len(routes) * c["num_experts"] * sparse \
            if name == "capacity" else routes[:, _route.FIELDS[name]].sum()
    return 100.0 * routes[:, _route.FIELDS[numer]].sum() / total \
        if total else None
