"""Entry points: the serving CLI (``serve``), the training loop
(``train``), device meshes and the persistent compile cache."""
from repro.launch import mesh  # noqa: F401
