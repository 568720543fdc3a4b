"""Operations and bytes of the benchmark's work, computed from shapes.

One module per kernel (``<kernel>.py``, with ``work(cfg)`` giving the
FLOPs and bytes of one sequence through one layer, forward and backward)
and ``model.py`` for the whole model's FLOPs per token.
"""
