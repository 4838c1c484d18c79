"""ButterFly BFS reproduction package (targets the installed JAX; see
``requirements.txt``)."""
