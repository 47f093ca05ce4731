"""JoyAI-LLM-Flash's language model (``joyai_llm_flash``): the
latent-attention model of ``latent_lm.py`` as it stands there, a sublayer's
output added to the one hidden state a token has. Everything (the latent
pools, the expert layers, the multi-token module, the paged protocol and
its nine device-side counts) is that module's; ``xing.py`` is the same
family with another residual stream."""

from .latent_lm import COUNTS, LatentLM

__all__ = ["JoyAILM", "COUNTS"]


class JoyAILM(LatentLM):
    """The language model. Widths default to the published ones."""
