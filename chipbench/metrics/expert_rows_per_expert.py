"""expert layer (models/layers.py apply_moe_held): rows each held expert
multiplies per decode step.

Delta of ``moe_rows`` (token-expert pairs the held experts computed in
decode, summed over layers and steps) over the delta of ``device_steps``
times the expert layers times the experts held, across the measured window.
Moves ``output_tok_s``: more rows per expert spread each expert's weight
stream over more tokens.  None where the program keeps no such counter or
the configuration holds no experts."""


def read(run):
    if "moe_rows" not in run.counters.get("close", {}):
        return None
    layers = run.dims.get("mlp_layer_types", [])[:run.dims.get(
        "num_hidden_layers", 0)].count("sparse")
    held = run.dims.get("num_experts", 0)
    steps = run.counter_delta("device_steps")
    if not (layers and held and steps):
        return None
    return run.counter_delta("moe_rows") / (steps * layers * held)
