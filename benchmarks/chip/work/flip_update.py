"""Least work of one probSAT flip over a window: what any implementation
of ``kernels/flip_update`` has to move, not the dense [K, B, C] tile the
present kernel rewrites.

Per chain (K candidates x B chains), for the flipped variable's O
occurrence slots: read the clause id (int32) and the literal sign (1
byte), read and write that clause's true count (int32), and write one
assignment byte. That is K*B*(13*O + 1) bytes and K*B*O compare-adds.
"""


def work(K: int, B: int, O: int):
    """(operations, bytes) of one flip_update call."""
    return K * B * O, K * B * (13 * O + 1)
