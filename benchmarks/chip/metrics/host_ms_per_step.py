"""Host time of one ``ContinuousBatcher.step``: the span of the step minus
the device's busy time inside it, averaged over the traced steps."""


def read(ctx):
    st = ctx.reduced.spans.get("step")
    if st is None or st.count == 0:
        return None
    return 1e3 * (st.seconds - st.busy_s) / st.count
