"""The bytes a chip has to send for the exchange of a data-parallel
iteration, whatever collectives carry it.

The program counts, on each ``train.block`` span, the collectives a chip
issued (``exchanges``) and the payload it put into them
(``exchange_bytes``: each one's operand, a sum's part). Every one of them
is a sum over the mesh, an all-reduce of that payload, whatever form the
program gives it. The least any all-reduce over P chips sends from each
chip is the ring's: 2 (P - 1) / P x the payload (a reduce-scatter and an
all-gather, each of (P - 1) / P of it). The bound is those bytes over the
chip's ICI peak (bench/peaks_ici.json): the exchange adds, it does not
multiply.
"""


def allreduce_bytes_sent(payload_bytes, chips):
    """Bytes one chip sends to all-reduce ``payload_bytes`` over ``chips``
    chips on a ring."""
    return 2.0 * (chips - 1) / chips * payload_bytes


def bound_seconds(payload_bytes, chips, ici_bytes_per_s):
    """The least time the chip could take to send them."""
    return allreduce_bytes_sent(payload_bytes, chips) / ici_bytes_per_s
