"""Ouro (``ouro``, a looped language model): its seeded weights, its plain
float32 reference, the operations and bytes its tokens need and what its
per-layer readers share, with the interfaces
``drivers/score_closed_family.py`` uses."""
