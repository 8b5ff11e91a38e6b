"""No input: the request runs on the solver's default (classic PageRank's
uniform teleport)."""


def draw(matrix: dict, request: dict, count: int, rng) -> list:
    return [None] * count
