"""Plain float32 references, written from the published descriptions and
the ByzSGD paper, importing nothing of the program under test."""
