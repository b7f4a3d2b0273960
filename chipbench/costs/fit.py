"""One whole L-BFGS fit of a coreset, for ``mfu.fit``: ``steps`` iterations,
each one fused value-and-gradient sweep and one Hessian-vector sweep over
the k coreset rows, counted from shapes whatever the fit does.

Per sweep over k rows of J columns, d = degree + 1 coefficients:

- the basis and its derivative: 8 operations a value, 16·k·J·d;
- forward: h̃ = A·ϑ and h̃′ = A′·ϑ, 4·k·J·d; z = h̃·Λᵀ, 2·k·J²;
- reverse: ∂ϑ from ∂h̃ and ∂h̃′, 4·k·J·d; ∂h̃ = ∂z·Λ and ∂Λ = ∂zᵀ·h̃, 4·k·J²;
- the Hessian-vector product (forward over reverse) repeats the basis and
  the primal forward and reverse, and adds their tangents: one product a
  contraction where only ϑ carries a tangent (8·k·J·d), two where both
  operands do (z, ∂h̃, ∂Λ: 12·k·J²).

Elementwise terms (squares, logarithms) and the host's L-BFGS step are
left out.
"""


def sweep_flops(cfg: dict, traffic: dict) -> dict:
    k, J, d = cfg["k"], cfg["J"], cfg["degree"] + 1
    basis = 16.0 * k * J * d
    primal = 8.0 * k * J * d + 6.0 * k * J * J
    tangent = 8.0 * k * J * d + 12.0 * k * J * J
    return {"vg": basis + primal, "hvp": basis + primal + tangent}


def flops(cfg: dict, traffic: dict) -> float:
    s = sweep_flops(cfg, traffic)
    return float(traffic["steps"]) * (s["vg"] + s["hvp"])
