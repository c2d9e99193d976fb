"""GLM-5.3-Flash (``glm5_next_text``): its seeded weights, its plain float32
reference, the operations and bytes its tokens need and what its per-layer
readers share, with the interfaces ``drivers/score_closed_family.py`` uses."""
