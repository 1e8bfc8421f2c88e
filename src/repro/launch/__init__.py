"""Launch layer: the ensemble-simulation serving loop
(``repro.launch.serve_sim``)."""
