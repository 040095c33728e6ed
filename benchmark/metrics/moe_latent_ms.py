"""Device time of the routed experts' latent projections per decode round:
the operations of the decode program (``programs.decode``) under
``MoEFFN/<node>/latent``, all layers and all steps of the round: the
projection of each token into the experts' latent space and of their
weighted sum back out. They run once a token, never once a token-expert
pair, so they read each layer's two latent matrices once a step. A program
without such scopes (one from before the latent space) gives None."""
from benchmark import scopes as S

MOE = S.under("MoEFFN/")


def read(ctx):
    return S.per_call_ms(ctx, "decode", lambda p: MOE(p) and "/latent" in p)
