"""Plain float32 references of the served models, one per attention
mechanism. They import nothing of the program: the weights are made again
from the seed by the same random draws that the served model's published
initialisation takes (normal draws scaled by 1/sqrt(fan-in), rounded to
the served dtype), and every layer is the textbook equation in
``jax.numpy`` at ``highest`` matmul precision, one layer at a time so the
reference fits beside nothing else on the chip."""
