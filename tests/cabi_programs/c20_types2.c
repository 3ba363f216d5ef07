/* Derived-datatype closure: the byte-granular
 * constructors (hvector/hindexed/struct), subarray, darray, and the
 * lb/extent model — a negative-stride vector round-trips through
 * Send/Recv with elements BEHIND the buffer pointer, the layout the
 * old flattened representation rejected (docs/CABI.md honest edges).
 * Reference: ompi/mpi/c/type_create_hvector.c.in, type_create_struct
 * .c.in, type_create_subarray.c.in, ompi/datatype/
 * ompi_datatype_create_darray.c. */
#include <mpi.h>
#include <stdio.h>
#include <stdlib.h>
#include <string.h>

static int rank, size;

#define CHECK(cond, code)                                            \
    do {                                                             \
        if (!(cond)) {                                               \
            fprintf(stderr, "rank %d: check failed at line %d\n",    \
                    rank, __LINE__);                                 \
            MPI_Abort(MPI_COMM_WORLD, code);                         \
        }                                                            \
    } while (0)

int main(int argc, char **argv)
{
    MPI_Init(&argc, &argv);
    MPI_Comm_rank(MPI_COMM_WORLD, &rank);
    MPI_Comm_size(MPI_COMM_WORLD, &size);
    CHECK(size >= 2, 1);
    /* even-odd pairs exchange; an odd-size tail rank skips the pt2pt
     * sections (paired = 0) but still runs every local/type check */
    int peer = rank ^ 1;
    int paired = peer < size;

    /* ---- negative-stride vector: elements behind the pointer ---- */
    MPI_Datatype negv;
    CHECK(MPI_Type_vector(3, 1, -2, MPI_INT, &negv) == MPI_SUCCESS, 2);
    MPI_Type_commit(&negv);
    MPI_Aint lb, extent, tlb, text;
    MPI_Type_get_extent(negv, &lb, &extent);
    CHECK(lb == (MPI_Aint)(-4 * sizeof(int)), 3);    /* -16 */
    MPI_Type_get_true_extent(negv, &tlb, &text);
    CHECK(tlb == lb && text == (MPI_Aint)(5 * sizeof(int)), 4);
    int tsz;
    MPI_Type_size(negv, &tsz);
    CHECK(tsz == 3 * (int)sizeof(int), 5);

    {
        int a[5] = {10, 11, 12, 13, 14}, b[5] = {0, 0, 0, 0, 0};
        /* significant elements of (&a[4], 1, negv): a[4], a[2], a[0] */
        if (!paired) {
            b[4] = 14; b[2] = 12; b[0] = 10;   /* local equivalent */
        } else if (rank % 2 == 0) {
            MPI_Send(&a[4], 1, negv, peer, 7, MPI_COMM_WORLD);
            MPI_Recv(&b[4], 1, negv, peer, 8, MPI_COMM_WORLD,
                     MPI_STATUS_IGNORE);
        } else {
            MPI_Recv(&b[4], 1, negv, peer, 7, MPI_COMM_WORLD,
                     MPI_STATUS_IGNORE);
            MPI_Send(&a[4], 1, negv, peer, 8, MPI_COMM_WORLD);
        }
        CHECK(b[4] == 14 && b[2] == 12 && b[0] == 10, 9);
        CHECK(b[1] == 0 && b[3] == 0, 10);           /* gaps intact */
    }

    /* ---- hvector: BYTE strides that misalign element boundaries
     * are legal (here: aligned but non-multiple-of-extent) -------- */
    MPI_Datatype hv;
    CHECK(MPI_Type_create_hvector(2, 2, 12, MPI_INT, &hv)
          == MPI_SUCCESS, 11);
    MPI_Type_commit(&hv);
    MPI_Type_size(hv, &tsz);
    CHECK(tsz == 4 * (int)sizeof(int), 12);
    {
        int src[6] = {1, 2, 3, 4, 5, 6}, dst[6] = {0};
        /* significant: src[0],src[1] and src[3],src[4] */
        if (!paired) {
            dst[0] = 1; dst[1] = 2; dst[3] = 4; dst[4] = 5;
        } else if (rank % 2 == 0) {
            MPI_Send(src, 1, hv, peer, 13, MPI_COMM_WORLD);
            MPI_Recv(dst, 1, hv, peer, 14, MPI_COMM_WORLD,
                     MPI_STATUS_IGNORE);
        } else {
            MPI_Recv(dst, 1, hv, peer, 13, MPI_COMM_WORLD,
                     MPI_STATUS_IGNORE);
            MPI_Send(src, 1, hv, peer, 14, MPI_COMM_WORLD);
        }
        CHECK(dst[0] == 1 && dst[1] == 2 && dst[3] == 4 && dst[4] == 5,
              15);
        CHECK(dst[2] == 0 && dst[5] == 0, 16);
    }

    /* ---- hindexed + struct (heterogeneous components) ----------- */
    {
        int bl[2] = {1, 2};
        MPI_Aint dis[2] = {4, 16};
        MPI_Datatype hi;
        CHECK(MPI_Type_create_hindexed(2, bl, dis, MPI_INT, &hi)
              == MPI_SUCCESS, 17);
        MPI_Type_commit(&hi);
        MPI_Type_size(hi, &tsz);
        CHECK(tsz == 3 * (int)sizeof(int), 18);
        MPI_Type_free(&hi);

        MPI_Aint disb[3] = {0, 8, 16};
        MPI_Datatype hib;
        CHECK(MPI_Type_create_hindexed_block(3, 1, disb, MPI_INT, &hib)
              == MPI_SUCCESS, 19);
        MPI_Type_commit(&hib);
        MPI_Type_size(hib, &tsz);
        CHECK(tsz == 3 * (int)sizeof(int), 20);
        MPI_Type_free(&hib);

        /* struct { char tag; double val; } with explicit padding */
        struct rec { char tag; char pad[7]; double val; };
        int sbl[2] = {1, 1};
        MPI_Aint sdis[2] = {0, 8};
        MPI_Datatype parts[2] = {MPI_CHAR, MPI_DOUBLE};
        MPI_Datatype st0, st;
        CHECK(MPI_Type_create_struct(2, sbl, sdis, parts, &st0)
              == MPI_SUCCESS, 21);
        /* pin the extent to sizeof(struct rec) the portable way */
        CHECK(MPI_Type_create_resized(st0, 0, sizeof(struct rec), &st)
              == MPI_SUCCESS, 22);
        MPI_Type_commit(&st);
        MPI_Type_size(st, &tsz);
        CHECK(tsz == 9, 23);
        MPI_Type_get_extent(st, &lb, &extent);
        CHECK(lb == 0 && extent == (MPI_Aint)sizeof(struct rec), 24);

        struct rec sa[3], sb[3];
        memset(sb, 0, sizeof(sb));
        for (int i = 0; i < 3; i++) {
            sa[i].tag = (char)('a' + i);
            sa[i].val = 1.5 * (i + 1) + rank;
        }
        if (!paired) {
            for (int i = 0; i < 3; i++)
                sb[i] = sa[i];
        } else if (rank % 2 == 0) {
            MPI_Send(sa, 3, st, peer, 25, MPI_COMM_WORLD);
            MPI_Recv(sb, 3, st, peer, 26, MPI_COMM_WORLD,
                     MPI_STATUS_IGNORE);
        } else {
            MPI_Recv(sb, 3, st, peer, 25, MPI_COMM_WORLD,
                     MPI_STATUS_IGNORE);
            MPI_Send(sa, 3, st, peer, 26, MPI_COMM_WORLD);
        }
        for (int i = 0; i < 3; i++) {
            CHECK(sb[i].tag == (char)('a' + i), 27);
            CHECK(sb[i].val == 1.5 * (i + 1) + (paired ? peer : rank),
                  28);
        }
        MPI_Type_free(&st0);
        MPI_Type_free(&st);
    }

    /* ---- subarray: 2x2 block of a 4x4, C order ------------------ */
    {
        int sizes[2] = {4, 4}, subs[2] = {2, 2}, starts[2] = {1, 1};
        MPI_Datatype sub;
        CHECK(MPI_Type_create_subarray(2, sizes, subs, starts,
                                       MPI_ORDER_C, MPI_INT, &sub)
              == MPI_SUCCESS, 29);
        MPI_Type_commit(&sub);
        MPI_Type_size(sub, &tsz);
        CHECK(tsz == 4 * (int)sizeof(int), 30);
        MPI_Type_get_extent(sub, &lb, &extent);
        CHECK(lb == 0 && extent == (MPI_Aint)(16 * sizeof(int)), 31);

        int g[16], h[16];
        for (int i = 0; i < 16; i++) {
            g[i] = 100 + i;
            h[i] = -1;
        }
        if (!paired) {
            h[5] = 105; h[6] = 106; h[9] = 109; h[10] = 110;
        } else if (rank % 2 == 0) {
            MPI_Send(g, 1, sub, peer, 32, MPI_COMM_WORLD);
            MPI_Recv(h, 1, sub, peer, 33, MPI_COMM_WORLD,
                     MPI_STATUS_IGNORE);
        } else {
            MPI_Recv(h, 1, sub, peer, 32, MPI_COMM_WORLD,
                     MPI_STATUS_IGNORE);
            MPI_Send(g, 1, sub, peer, 33, MPI_COMM_WORLD);
        }
        /* positions (1,1),(1,2),(2,1),(2,2) = flat 5,6,9,10 */
        CHECK(h[5] == 105 && h[6] == 106 && h[9] == 109 && h[10] == 110,
              34);
        CHECK(h[0] == -1 && h[4] == -1 && h[15] == -1, 35);
        MPI_Type_free(&sub);
    }

    /* ---- darray: 1-D BLOCK over the job, then 2-D block x cyclic - */
    {
        int g1 = 4 * size;
        int gsz[1] = {g1};
        int dist[1] = {MPI_DISTRIBUTE_BLOCK};
        int darg[1] = {MPI_DISTRIBUTE_DFLT_DARG};
        int psz[1] = {size};
        MPI_Datatype da;
        CHECK(MPI_Type_create_darray(size, rank, 1, gsz, dist, darg,
                                     psz, MPI_ORDER_C, MPI_INT, &da)
              == MPI_SUCCESS, 36);
        MPI_Type_commit(&da);
        MPI_Type_size(da, &tsz);
        CHECK(tsz == 4 * (int)sizeof(int), 37);      /* my block */
        MPI_Type_get_extent(da, &lb, &extent);
        CHECK(extent == (MPI_Aint)(g1 * sizeof(int)), 38);

        /* pack my portion out of the global array: block k owns
         * [4k, 4k+4) */
        int *glob = malloc(g1 * sizeof(int));
        for (int i = 0; i < g1; i++)
            glob[i] = 1000 + i;
        int psize = 0;
        MPI_Pack_size(1, da, MPI_COMM_WORLD, &psize);
        CHECK(psize >= tsz, 39);
        char *pk = malloc(psize);
        int pos = 0;
        CHECK(MPI_Pack(glob, 1, da, pk, psize, &pos, MPI_COMM_WORLD)
              == MPI_SUCCESS, 40);
        CHECK(pos == tsz, 41);
        int *vals = (int *)pk;
        for (int i = 0; i < 4; i++)
            CHECK(vals[i] == 1000 + 4 * rank + i, 42);
        free(pk);
        free(glob);
        MPI_Type_free(&da);
    }
    {
        /* 2-D: 4x6 ints over a 1 x size grid, dim0 BLOCK, dim1
         * CYCLIC(1) — checked against a direct loop */
        int gsz[2] = {4, 6};
        int dist[2] = {MPI_DISTRIBUTE_BLOCK, MPI_DISTRIBUTE_CYCLIC};
        int darg[2] = {MPI_DISTRIBUTE_DFLT_DARG, 1};
        int psz[2] = {1, size};
        MPI_Datatype da2;
        CHECK(MPI_Type_create_darray(size, rank, 2, gsz, dist, darg,
                                     psz, MPI_ORDER_C, MPI_INT, &da2)
              == MPI_SUCCESS, 43);
        MPI_Type_commit(&da2);
        int mycols = 0;
        for (int c = 0; c < 6; c++)
            if (c % size == rank)
                mycols++;
        MPI_Type_size(da2, &tsz);
        CHECK(tsz == 4 * mycols * (int)sizeof(int), 44);

        int glob[24], pos = 0, psize = 0;
        for (int i = 0; i < 24; i++)
            glob[i] = 2000 + i;
        MPI_Pack_size(1, da2, MPI_COMM_WORLD, &psize);
        char *pk = malloc(psize > 0 ? psize : 1);
        CHECK(MPI_Pack(glob, 1, da2, pk, psize, &pos, MPI_COMM_WORLD)
              == MPI_SUCCESS, 45);
        int *vals = (int *)pk, k = 0;
        for (int r2 = 0; r2 < 4; r2++)
            for (int c = 0; c < 6; c++)
                if (c % size == rank)
                    CHECK(vals[k++] == 2000 + 6 * r2 + c, 46);
        CHECK(k == 4 * mycols, 47);
        free(pk);
        MPI_Type_free(&da2);
    }

    /* ---- Get_elements through a derived type -------------------- */
    {
        MPI_Status st;
        int payload[4] = {1, 2, 3, 4}, got[8];
        MPI_Datatype two;
        MPI_Type_contiguous(2, MPI_INT, &two);
        MPI_Type_commit(&two);
        if (paired) {
            if (rank % 2 == 0) {
                MPI_Send(payload, 2, two, peer, 48, MPI_COMM_WORLD);
                MPI_Recv(got, 4, two, peer, 49, MPI_COMM_WORLD, &st);
            } else {
                MPI_Recv(got, 4, two, peer, 48, MPI_COMM_WORLD, &st);
                MPI_Send(payload, 2, two, peer, 49, MPI_COMM_WORLD);
            }
            int cnt = -1, el = -1;
            MPI_Get_count(&st, two, &cnt);
            MPI_Get_elements(&st, two, &el);
            CHECK(cnt == 2 && el == 4, 50);
        }
        MPI_Type_free(&two);
    }

    MPI_Type_free(&negv);
    MPI_Type_free(&hv);
    printf("OK c20_types2 rank=%d/%d\n", rank, size);
    MPI_Finalize();
    return 0;
}
