"""The work of a count-min update, from its shapes: ``M`` (key, weight)
items hashed into each of ``depth`` rows of a ``width``-wide table.
Each item is read once (a 4-byte key and a 4-byte weight) and each row
takes one multiply, one shift and one add per item; the table is
written once. Whatever implements it (a scatter, a one-hot matmul) does
at least this."""


def cost(shapes):
    """``shapes``: the operation's result then its operands, as
    ``(dtype, dims)``: result f32[depth, width], keys and weights with
    ``M`` elements in all. Returns ``(ops, bytes)``."""
    (_, (depth, width)), (_, keys), (_, _weights) = shapes[:3]
    items = 1
    for d in keys:
        items *= d
    return 3 * items * depth, 8 * items + 4 * depth * width
