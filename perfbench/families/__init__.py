"""What the harness has to know about a model FAMILY, one file a family:
`<model_type>.py`, found by the `model_type` of the configuration's
published keys (`children.py::family_of`). A family file gives three names:

    layer_plan(hf, i)    the tensors of layer i of a model with the keys hf
    outside_plan(hf)     the tensors outside the layers
    logits(path, seed, serving) -> dict
                         the check that decides `correct` (c), on the
                         checkpoint at path cut to serving.logits_check_layers
                         layers; the keys run.py reads are `ok`, `platform`
                         and `compared`, the rest is printed as it comes

A plan is a list of `(name, shape, scale)` in the order the tensors are
drawn: a number draws the tensor uniform with that standard deviation
(`children.py::draw`), None fills it with ones. It is data, so the harness
can lay the plan of a shallower model beside the full one's: a shard whose
plan is the same is the same bytes (same seed) and is linked, one whose plan
differs (a layer whose kind depends on the depth) is written. A family
states its own bounds, each with its reason, beside its `logits`.

A file whose name starts with `_` is shared by families and is none itself.
"""
