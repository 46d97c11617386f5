"""Least work of the event apply, per chip and window.

Each delivered event reaches the synapses of its source neuron onto the
node: per synapse the target index and the weight are read (4 + 4 B)
and the ring entry of its arrival slot is read and written (4 + 4 B),
one add; per event its 8-byte wire word is read.  Synapses per event are
the node's mean over the source neurons that reach it.  Bytes bound it.
"""


def work(ctx):
    delivered = ctx["stats"]["delivered"]              # (windows, nodes)
    synapses = (delivered * ctx["network"]["syn_per_event"][None, :]).sum()
    n = delivered.size
    return {"flops": synapses / n,
            "bytes": (16 * synapses + 8 * delivered.sum()) / n}
