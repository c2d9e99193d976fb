"""MiMo-V2-Flash (``mimo_v2_flash``): its seeded weights, its plain float32
reference and the operations and bytes its tokens need, with the interfaces
``drivers/score_closed.py`` uses of the ``deepseek_v3`` modules at the
benchmark's top level."""
