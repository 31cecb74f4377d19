"""No FFN: the layer is its mixer alone, with no second norm."""
ROLE = "ffn"
SPEC = "none"
KEYS = ()
SUBKEY = None


def arch_fields(a: dict) -> dict:
    return {}


def matmul_params(a: dict) -> int:
    return 0


def flops_fwd(a: dict, batch: int, seq: int) -> int:
    return 0
