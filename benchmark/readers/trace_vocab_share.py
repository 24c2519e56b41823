"""Share of the device's busy time spent in operations that read or write
an array with a dimension of the vocabulary's size (``readers.
trace_op_share`` with the pattern made from the configuration): a trace
prints an operation as its whole HLO line, operands' and result's shapes
among it, and the head's product, the reductions over its logits and the
embedding's gather are the operations whose shapes name the vocabulary.
``exclude`` keeps out the operations that contain others (the chained
dispatch's ``while`` carries every parameter)."""
from readers import trace_op_share


def read(ctx, exclude="^$"):
    size = ctx["config"].get("vocab_size")
    if not size:
        return None
    return trace_op_share.read(ctx, rf"[\[,]{int(size)}[\],]", exclude)
