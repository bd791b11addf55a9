"""The work of a quantile compaction, from its shapes: ``P`` value-sorted
summary slots (value, exclusive and inclusive cumulative weight) swept
once against ``C`` sorted rank targets, a merge of two sorted lists:
one comparison per slot and per target, each slot's three floats read
once, the targets read and the picked values written once."""


def cost(shapes):
    """``shapes``: result f32[1, C] then operands values, cumw_prev,
    cumw (``P`` elements each) and targets f32[1, C]. Returns
    ``(ops, bytes)``."""
    (_, res), (_, vals) = shapes[:2]
    slots = 1
    for d in vals:
        slots *= d
    targets = 1
    for d in res:
        targets *= d
    return slots + targets, 12 * slots + 8 * targets
