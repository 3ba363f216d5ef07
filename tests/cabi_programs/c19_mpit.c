/* MPI_T from C: enumerate control variables, read and WRITE one (the
 * algorithm-selection knob — a tool retuning the library at runtime),
 * and read performance counters that move with traffic
 * (ompi/mpi/tool/* + the SPC pvar surface). */
#include <mpi.h>
#include <stdio.h>
#include <string.h>

#define CHECK(cond, code)                                            \
    do {                                                             \
        if (!(cond)) {                                               \
            fprintf(stderr, "rank %d: check failed at line %d\n",    \
                    rank, __LINE__);                                 \
            MPI_Abort(MPI_COMM_WORLD, code);                         \
        }                                                            \
    } while (0)

/* event tool state: the callback reads the instance's one element */
static volatile int g_event_fires;
static volatile unsigned long long g_event_value;

static void event_cb(MPI_T_event_instance instance,
                     MPI_T_event_registration reg,
                     MPI_T_cb_safety safety, void *user_data)
{
    (void)reg;
    (void)safety;
    (void)user_data;
    unsigned long long v = 0;
    if (MPI_T_event_read(instance, 0, &v) == MPI_SUCCESS)
        g_event_value = v;
    g_event_fires++;
}

int main(int argc, char **argv)
{
    int rank, size, provided = -1;
    MPI_T_init_thread(MPI_THREAD_SINGLE, &provided);
    CHECK(provided == MPI_THREAD_MULTIPLE, 1);
    /* MPI_T is usable BEFORE MPI_Init (tools enumerate early) */
    int early = -1;
    CHECK(MPI_T_cvar_get_num(&early) == MPI_SUCCESS && early >= 0, 30);
    /* and out-of-range probes RETURN, never abort */
    char nm[64];
    int nl = sizeof(nm), verb, bind, scope;
    MPI_Datatype edt;
    MPI_T_enum een;
    char eds[64];
    int edl = sizeof(eds);
    CHECK(MPI_T_cvar_get_info(1 << 28, nm, &nl, &verb, &edt, &een,
                              eds, &edl, &bind, &scope)
          == MPI_T_ERR_INVALID_INDEX, 31);
    MPI_Init(&argc, &argv);
    MPI_Comm_rank(MPI_COMM_WORLD, &rank);
    MPI_Comm_size(MPI_COMM_WORLD, &size);

    /* ---- cvars: enumerate, find by name, read, write ---- */
    int ncvar = -1;
    MPI_T_cvar_get_num(&ncvar);
    CHECK(ncvar > 10, 2);
    char name[128], desc[256];
    int name_len = sizeof(name), desc_len = sizeof(desc);
    MPI_Datatype dt;
    MPI_T_enum en;
    MPI_T_cvar_get_info(0, name, &name_len, &verb, &dt, &en, desc,
                        &desc_len, &bind, &scope);
    CHECK(name[0] != '\0', 3);

    int idx = -1;
    CHECK(MPI_T_cvar_get_index("coll_xla_allreduce_algorithm", &idx)
          == MPI_SUCCESS && idx >= 0, 4);
    /* indices are stable: the same name resolves to the same index */
    int idx2 = -1;
    MPI_T_cvar_get_index("coll_xla_allreduce_algorithm", &idx2);
    CHECK(idx2 == idx, 5);

    MPI_T_cvar_handle ch;
    int count = -1;
    MPI_T_cvar_handle_alloc(idx, NULL, &ch, &count);
    /* string cvar: count advertises the read capacity the caller
     * must provide (the MPI_T buffer-sizing contract) */
    CHECK(count == 256, 6);
    char val[256] = {0};
    MPI_T_cvar_read(ch, val);
    CHECK(strcmp(val, "auto") == 0, 7);
    /* a tool retunes the library: write, reread, restore */
    MPI_T_cvar_write(ch, "hier");
    MPI_T_cvar_read(ch, val);
    CHECK(strcmp(val, "hier") == 0, 8);
    MPI_T_cvar_write(ch, "auto");
    MPI_T_cvar_handle_free(&ch);

    /* an integer-typed cvar round-trips through the int marshalling */
    CHECK(MPI_T_cvar_get_index("coll_xla_cache_max_entries", &idx)
          == MPI_SUCCESS, 9);
    MPI_T_cvar_handle_alloc(idx, NULL, &ch, &count);
    int cap = -1;
    MPI_T_cvar_read(ch, &cap);
    CHECK(cap == 256, 10);
    int newcap = 128;
    MPI_T_cvar_write(ch, &newcap);
    MPI_T_cvar_read(ch, &cap);
    CHECK(cap == 128, 11);
    newcap = 256;
    MPI_T_cvar_write(ch, &newcap);
    MPI_T_cvar_handle_free(&ch);

    /* unknown names fail with the MPI_T error class */
    CHECK(MPI_T_cvar_get_index("no_such_var_xyz", &idx)
          == MPI_T_ERR_INVALID_NAME, 12);

    /* ---- pvars: counters move with traffic ---- */
    /* counters surface lazily with their subsystem's first use */
    int warm = 1, wsum = 0;
    MPI_Allreduce(&warm, &wsum, 1, MPI_INT, MPI_SUM, MPI_COMM_WORLD);
    int npvar = -1;
    MPI_T_pvar_get_num(&npvar);
    CHECK(npvar > 0, 13);
    int pidx = -1;
    CHECK(MPI_T_pvar_get_index("spc_coll_allreduce", &pidx)
          == MPI_SUCCESS, 14);
    MPI_T_pvar_session ses;
    MPI_T_pvar_session_create(&ses);
    MPI_T_pvar_handle ph;
    MPI_T_pvar_handle_alloc(ses, pidx, NULL, &ph, &count);
    MPI_T_pvar_start(ses, ph);
    unsigned long long before = 0, after = 0;
    MPI_T_pvar_read(ses, ph, &before);
    int v = rank, s = -1;
    MPI_Allreduce(&v, &s, 1, MPI_INT, MPI_SUM, MPI_COMM_WORLD);
    MPI_Allreduce(&v, &s, 1, MPI_INT, MPI_SUM, MPI_COMM_WORLD);
    MPI_T_pvar_read(ses, ph, &after);
    CHECK(after >= before + 2, 15);
    MPI_T_pvar_stop(ses, ph);

    /* ---- pvar WRITE: SPC counters accept tool writes ---- */
    unsigned long long wrote = 4242;
    CHECK(MPI_T_pvar_write(ses, ph, &wrote) == MPI_SUCCESS, 16);
    unsigned long long back = 0;
    MPI_T_pvar_read(ses, ph, &back);
    CHECK(back == 4242, 17);
    MPI_T_pvar_handle_free(ses, &ph);
    MPI_T_pvar_session_free(&ses);

    /* ---- categories: variables group by framework ---- */
    int ncat = -1;
    CHECK(MPI_T_category_get_num(&ncat) == MPI_SUCCESS && ncat > 3,
          40);
    int ci = -1;
    CHECK(MPI_T_category_get_index("coll", &ci) == MPI_SUCCESS
          && ci >= 0, 41);
    char cname[64], cdesc[128];
    int cnl = sizeof(cname), cdl = sizeof(cdesc);
    int ncv = -1, npv = -1, ncc = -1;
    CHECK(MPI_T_category_get_info(ci, cname, &cnl, cdesc, &cdl, &ncv,
                                  &npv, &ncc) == MPI_SUCCESS, 42);
    CHECK(strcmp(cname, "coll") == 0 && ncv > 5, 43);
    int cvars[256];
    CHECK(ncv <= 256, 44);
    CHECK(MPI_T_category_get_cvars(ci, ncv, cvars) == MPI_SUCCESS, 45);
    /* every member index resolves to a cvar whose name starts with
     * the category */
    char vn[128];
    int vnl = sizeof(vn), vverb, vbind, vscope;
    MPI_Datatype vdt;
    MPI_T_enum ven;
    char vds[64];
    int vdl = sizeof(vds);
    CHECK(MPI_T_cvar_get_info(cvars[0], vn, &vnl, &vverb, &vdt, &ven,
                              vds, &vdl, &vbind, &vscope)
          == MPI_SUCCESS, 46);
    CHECK(strncmp(vn, "coll", 4) == 0, 47);
    int stamp = -1;
    CHECK(MPI_T_category_changed(&stamp) == MPI_SUCCESS
          && stamp == ncat, 48);

    /* ---- events: bind a C callback to coll_allreduce ---- */
    int nev = -1;
    CHECK(MPI_T_event_get_num(&nev) == MPI_SUCCESS && nev > 0, 18);
    int eidx = -1;
    CHECK(MPI_T_event_get_index("coll_allreduce", &eidx)
          == MPI_SUCCESS && eidx >= 0, 19);
    char ename[64], edesc[128], einfo[8];
    int enl = sizeof(ename), edsl = sizeof(edesc),
        eil = sizeof(einfo);
    int everb = -1, enelem = -1, ebind = -1;
    MPI_Datatype etypes;
    MPI_T_enum eenum;
    CHECK(MPI_T_event_get_info(eidx, ename, &enl, &everb, &etypes,
                               &enelem, &eenum, einfo, &eil, edesc,
                               &edsl, &ebind) == MPI_SUCCESS, 20);
    CHECK(strcmp(ename, "coll_allreduce") == 0 && enelem == 1, 21);
    MPI_T_event_registration ereg;
    CHECK(MPI_T_event_handle_alloc(eidx, NULL, MPI_INFO_NULL,
                                   event_cb, NULL, &ereg)
          == MPI_SUCCESS, 22);
    g_event_fires = 0;
    g_event_value = 0;
    int ev = rank, es = -1;
    MPI_Allreduce(&ev, &es, 1, MPI_INT, MPI_SUM, MPI_COMM_WORLD);
    CHECK(g_event_fires >= 1, 23);
    CHECK(g_event_value == (unsigned long long)sizeof(int), 24);
    CHECK(MPI_T_event_handle_free(ereg, NULL, NULL) == MPI_SUCCESS,
          25);
    g_event_fires = 0;
    MPI_Allreduce(&ev, &es, 1, MPI_INT, MPI_SUM, MPI_COMM_WORLD);
    CHECK(g_event_fires == 0, 26);       /* unbound: no more fires */

    printf("OK c19_mpit rank=%d/%d\n", rank, size);
    MPI_Finalize();
    MPI_T_finalize();
    return 0;
}
